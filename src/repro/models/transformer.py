"""Decoder-only LM assembly: period-scan over heterogeneous layer stacks.

The layer pattern of every assigned arch repeats with a small period P
(qwen2: 1; llama4: 2 dense/MoE; jamba: 8 = 1 attn + 7 mamba with MoE every
2nd; xlstm: 8 = 1 sLSTM + 7 mLSTM). Parameters are stacked per
period-position — each leaf [n_periods, ...] — and the trunk is one
``lax.scan`` over periods with the P positions unrolled inside. This keeps
the HLO compact (one loop regardless of depth: 80-layer qwen2 lowers the
same graph as an 8-layer one), which matters for the 512-device dry-run
compiles, and gives remat a natural per-period boundary.

Three entry points per model (built by :func:`repro.models.model.build_model`):
``train_forward`` (loss), ``prefill`` (tokens -> last logits + decode state),
``decode_step`` (one token + state -> logits + state). Decode state mirrors
the parameter stacking: per-position leaves [n_periods, ...]; attention
positions carry KV caches (full or SWA rolling buffer), mamba/xlstm carry
their O(1) recurrent states.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from .attention import blocked_attention, decode_attention
from .config import ModelConfig
from .layers import (apply_mlp, apply_norm, apply_rotary, chunked_ce_loss,
                     dense_init, embed_init, mlp_init, mrope_angles,
                     norm_init, rope_angles)
from .mamba import apply_mamba, mamba_decode_step, mamba_init
from .moe import apply_moe, moe_init
from .xlstm import (apply_mlstm, apply_slstm, mlstm_decode_step, mlstm_init,
                    mlstm_state_init, slstm_decode_step, slstm_init)


# --------------------------------------------------------------------------
# per-kind block init
# --------------------------------------------------------------------------
def _attn_init(rng, cfg: ModelConfig, dtype):
    d, h = cfg.d_model, cfg.head_dim
    ks = jax.random.split(rng, 4)
    wq, aq = dense_init(ks[0], d, cfg.n_heads * h, ("embed", "heads"), dtype)
    wk, ak = dense_init(ks[1], d, cfg.n_kv_heads * h, ("embed", "kv_heads"), dtype)
    wv, av = dense_init(ks[2], d, cfg.n_kv_heads * h, ("embed", "kv_heads"), dtype)
    wo, ao = dense_init(ks[3], cfg.n_heads * h, d, ("heads", "embed"), dtype)
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    s = {"wq": aq, "wk": ak, "wv": av, "wo": ao}
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((cfg.n_heads * h,), dtype),
                 bk=jnp.zeros((cfg.n_kv_heads * h,), dtype),
                 bv=jnp.zeros((cfg.n_kv_heads * h,), dtype))
        s.update(bq=("heads",), bk=("kv_heads",), bv=("kv_heads",))
    return p, s


def _block_init(rng, cfg: ModelConfig, kind: dict, dtype):
    kn, km, kf = jax.random.split(rng, 3)
    p: dict = {}
    s: dict = {}
    p["norm1"], s["norm1"] = norm_init(cfg.d_model, cfg.norm, dtype)
    mix = kind["mix"]
    if mix == "attn":
        p["mix"], s["mix"] = _attn_init(km, cfg, dtype)
    elif mix == "mamba":
        p["mix"], s["mix"] = mamba_init(km, cfg.d_model, cfg.mamba_expand,
                                        cfg.mamba_d_state, cfg.mamba_d_conv, dtype)
    elif mix == "mlstm":
        p["mix"], s["mix"] = mlstm_init(km, cfg.d_model, cfg.n_heads,
                                        cfg.xlstm_proj_factor, cfg.xlstm_conv, dtype)
    elif mix == "slstm":
        p["mix"], s["mix"] = slstm_init(km, cfg.d_model, cfg.n_heads, dtype)
    if kind["ff"] == "mlp":
        p["norm2"], s["norm2"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["ff"], s["ff"] = mlp_init(kf, cfg.d_model, cfg.d_ff, dtype)
    elif kind["ff"] == "moe":
        p["norm2"], s["norm2"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["ff"], s["ff"] = moe_init(kf, cfg.d_model, cfg.ff_expert,
                                    cfg.n_experts, cfg.n_shared_experts, dtype)
    return p, s


def init_params(rng, cfg: ModelConfig) -> tuple[dict, dict]:
    """Build (params, logical-axis specs); period leaves stacked [n_periods,...]."""
    dtype = jnp.dtype(cfg.dtype)
    P = cfg.scan_period()
    n_periods = cfg.n_layers // P
    kinds = cfg.layer_kinds()[:P]
    k_emb, k_blocks, k_head = jax.random.split(rng, 3)

    params: dict = {}
    specs: dict = {}
    params["embed"], specs["embed"] = embed_init(k_emb, cfg.padded_vocab,
                                                 cfg.d_model, dtype)
    blocks_p, blocks_s = [], []
    for pos in range(P):
        keys = jax.random.split(jax.random.fold_in(k_blocks, pos), n_periods)
        stacked = jax.vmap(lambda k: _block_init(k, cfg, kinds[pos], dtype)[0])(keys)
        _, spec = _block_init(keys[0], cfg, kinds[pos], dtype)
        spec = jax.tree.map(lambda ax: ("layers",) + tuple(ax),
                            spec, is_leaf=lambda x: isinstance(x, tuple))
        blocks_p.append(stacked)
        blocks_s.append(spec)
    params["period"] = tuple(blocks_p)
    specs["period"] = tuple(blocks_s)
    params["final_norm"], specs["final_norm"] = norm_init(cfg.d_model, cfg.norm,
                                                          dtype)
    if not cfg.tie_embeddings:
        params["lm_head"], specs["lm_head"] = dense_init(
            k_head, cfg.d_model, cfg.padded_vocab, ("embed", "vocab"), dtype)
    return params, specs


# --------------------------------------------------------------------------
# forward (train / prefill)
# --------------------------------------------------------------------------
def _angles_for(cfg: ModelConfig, positions, batch=None):
    if cfg.rope_type == "none":
        return None
    if cfg.rope_type == "mrope":
        p3 = None if batch is None else batch.get("positions3")
        if p3 is None:
            p3 = jnp.broadcast_to(positions, (3,) + positions.shape)
        return mrope_angles(p3, cfg.head_dim, cfg.rope_theta,
                            cfg.mrope_sections)
    return rope_angles(positions, cfg.head_dim, cfg.rope_theta)


def _qkv(p, cfg: ModelConfig, y, angles):
    B, S, _ = y.shape
    h = cfg.head_dim
    q = y @ p["wq"] + (p["bq"] if "bq" in p else 0)
    k = y @ p["wk"] + (p["bk"] if "bk" in p else 0)
    v = y @ p["wv"] + (p["bv"] if "bv" in p else 0)
    q = q.reshape(B, S, cfg.n_heads, h)
    k = k.reshape(B, S, cfg.n_kv_heads, h)
    v = v.reshape(B, S, cfg.n_kv_heads, h)
    if angles is not None:
        a = angles if angles.ndim == 3 else angles[None]     # [B,S,half]
        q = apply_rotary(q, a[:, :, None, :])
        k = apply_rotary(k, a[:, :, None, :])
    return q, k, v


def _block_apply(p, kind, cfg: ModelConfig, x, angles, collect_state: bool,
                 dropless: bool = False):
    """One block, train/prefill. Returns (x, aux_loss, state_or_None).

    ``collect_state`` (prefill) captures what decode needs: roped K/V for
    attention positions, the final recurrent carry for mamba/xlstm positions.
    ``dropless`` routes MoE blocks without capacity drops (inference paths;
    see :func:`repro.models.moe.apply_moe`).
    """
    from repro.perf_flags import enabled as _perf
    from repro.distributed.activations import matmul_input_constraint
    y = apply_norm(p["norm1"], x, cfg.norm, cfg.norm_eps)
    if _perf("mm_gather"):
        y = matmul_input_constraint(y)
    aux = jnp.float32(0)
    st = None
    mix = kind["mix"]
    if mix == "attn":
        q, k, v = _qkv(p["mix"], cfg, y, angles)
        from repro.perf_flags import enabled
        if enabled("attn_reshard"):
            from repro.distributed.activations import attn_constraint
            q, k, v = attn_constraint(q, k, v)
        o = blocked_attention(q, k, v, causal=True,
                              window=cfg.sliding_window,
                              softcap=cfg.attn_logit_softcap,
                              block_k=2048 if enabled("blockk") else 512)
        B, S = x.shape[:2]
        x = x + o.reshape(B, S, -1) @ p["mix"]["wo"]
        if collect_state:
            st = {"k": k, "v": v}
    elif mix == "mamba":
        r = apply_mamba(p["mix"], y, cfg.mamba_d_state, collect_state)
        x, st = (x + r[0], r[1]) if collect_state else (x + r, None)
    elif mix == "mlstm":
        r = apply_mlstm(p["mix"], y, cfg.n_heads, cfg.xlstm_conv, collect_state)
        x, st = (x + r[0], r[1]) if collect_state else (x + r, None)
    elif mix == "slstm":
        r = apply_slstm(p["mix"], y, cfg.n_heads, collect_state)
        x, st = (x + r[0], r[1]) if collect_state else (x + r, None)
    if kind["ff"] == "mlp":
        y2 = apply_norm(p["norm2"], x, cfg.norm, cfg.norm_eps)
        if _perf("mm_gather"):
            y2 = matmul_input_constraint(y2)
        x = x + apply_mlp(p["ff"], y2, cfg.act)
    elif kind["ff"] == "moe":
        y2 = apply_norm(p["norm2"], x, cfg.norm, cfg.norm_eps)
        if _perf("mm_gather"):
            y2 = matmul_input_constraint(y2)
        o, a = apply_moe(p["ff"], y2, cfg.top_k, cfg.capacity_factor, cfg.act,
                         dropless=dropless)
        x, aux = x + o, aux + a
    return x, aux, st


def forward_hidden(params, cfg: ModelConfig, x, positions, batch=None,
                   collect_state: bool = False, dropless: bool = False):
    """Trunk: embedded input [B,S,D] -> (hidden, aux, per-position states)."""
    P = cfg.scan_period()
    kinds = cfg.layer_kinds()[:P]
    angles = _angles_for(cfg, positions, batch)

    from repro.distributed.activations import activation_constraint

    def period(carry, pp):
        x, aux = carry
        sts = []
        for pos in range(P):
            x, a, st = _block_apply(pp[pos], kinds[pos], cfg, x,
                                    angles, collect_state, dropless)
            aux = aux + a
            sts.append(st)
        return (activation_constraint(x), aux), tuple(sts)

    body = jax.checkpoint(period) if cfg.remat else period
    (x, aux), state_stacks = jax.lax.scan(body, (x, jnp.float32(0)),
                                          params["period"])
    return x, aux, state_stacks


def embed_tokens(params, cfg: ModelConfig, tokens):
    return params["embed"][tokens].astype(jnp.dtype(cfg.dtype))


def lm_head(params, cfg: ModelConfig):
    return (params["lm_head"] if not cfg.tie_embeddings
            else params["embed"].T)


def train_forward(params, cfg: ModelConfig, batch) -> jax.Array:
    """batch: tokens/targets/mask [B,S] (+ 'embeds' for stub frontends)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = (batch["embeds"].astype(jnp.dtype(cfg.dtype)) if "embeds" in batch
         else embed_tokens(params, cfg, tokens))
    positions = jnp.arange(S)
    h, aux, _ = forward_hidden(params, cfg, x, positions, batch)
    h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
    loss = chunked_ce_loss(h, lm_head(params, cfg), batch["targets"],
                           batch["mask"])
    return loss + 0.01 * aux


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------
def _cache_len(cfg: ModelConfig, max_len: int) -> int:
    return min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len


def init_decode_state(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    """Zeroed decode state; prefill fills it, dry-run lowers its specs."""
    dtype = jnp.dtype(cfg.dtype)
    P = cfg.scan_period()
    n_periods = cfg.n_layers // P
    kinds = cfg.layer_kinds()[:P]
    T = _cache_len(cfg, max_len)

    def one(kind):
        mix = kind["mix"]
        if mix == "attn":
            sh = (batch_size, T, cfg.n_kv_heads, cfg.head_dim)
            return {"k": jnp.zeros(sh, dtype), "v": jnp.zeros(sh, dtype)}
        if mix == "mamba":
            di = cfg.mamba_expand * cfg.d_model
            return {"conv": jnp.zeros((batch_size, cfg.mamba_d_conv - 1, di), dtype),
                    "h": jnp.zeros((batch_size, di, cfg.mamba_d_state), jnp.float32)}
        if mix == "mlstm":
            di = int(cfg.xlstm_proj_factor * cfg.d_model)
            dh = di // cfg.n_heads
            return {"conv": jnp.zeros((batch_size, cfg.xlstm_conv - 1, di), dtype),
                    "C": jnp.zeros((batch_size, cfg.n_heads, dh, dh), jnp.float32),
                    "n": jnp.zeros((batch_size, cfg.n_heads, dh), jnp.float32),
                    "m": jnp.zeros((batch_size, cfg.n_heads), jnp.float32)}
        if mix == "slstm":
            return {k: jnp.zeros((batch_size, cfg.d_model), jnp.float32)
                    for k in ("h", "c", "n", "m")}
        return {}

    blocks = tuple(jax.tree.map(
        lambda t: jnp.broadcast_to(t, (n_periods,) + t.shape).copy(), one(k))
        for k in kinds)
    return {"blocks": blocks, "pos": jnp.zeros((), jnp.int32)}


def decode_state_specs(cfg: ModelConfig) -> dict:
    """Logical axes for the decode state (mirrors init_decode_state)."""
    P = cfg.scan_period()
    kinds = cfg.layer_kinds()[:P]

    def one(kind):
        mix = kind["mix"]
        if mix == "attn":
            kv = ("layers", "batch", "kv_seq", "kv_heads_s", None)
            return {"k": kv, "v": kv}
        if mix == "mamba":
            return {"conv": ("layers", "batch", None, "inner"),
                    "h": ("layers", "batch", "inner", None)}
        if mix == "mlstm":
            return {"conv": ("layers", "batch", None, "inner"),
                    "C": ("layers", "batch", None, None, None),
                    "n": ("layers", "batch", None, None),
                    "m": ("layers", "batch", None)}
        if mix == "slstm":
            return {k: ("layers", "batch", "embed") for k in ("h", "c", "n", "m")}
        return {}

    return {"blocks": tuple(one(k) for k in kinds), "pos": ()}


def _attn_decode(p, cfg: ModelConfig, y, st, pos, angles):
    B = y.shape[0]
    q, k, v = _qkv(p, cfg, y, angles)                    # S=1
    T = st["k"].shape[1]
    slot = jnp.mod(pos, T) if cfg.sliding_window else pos
    length = jnp.minimum(pos + 1, T)
    if jnp.ndim(pos):
        # a position per row: each row writes its own slot; a row at a
        # negative position writes nothing and attends over nothing
        live = pos >= 0
        at = (live[:, None] & (jnp.arange(T)[None, :] == slot[:, None]))
        at = at[:, :, None, None]
        k_cache = jnp.where(at, k, st["k"])
        v_cache = jnp.where(at, v, st["v"])
        length = jnp.where(live, length, 0)
    else:
        k_cache = jax.lax.dynamic_update_slice(st["k"], k, (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(st["v"], v, (0, slot, 0, 0))
    o = decode_attention(q, k_cache, v_cache, length,
                         softcap=cfg.attn_logit_softcap)
    return o.reshape(B, 1, -1) @ p["wo"], {"k": k_cache, "v": v_cache}


def decode_step(params, cfg: ModelConfig, token, state, embeds=None):
    """One token for every stream: token [B] int32 -> (logits [B,V], state).

    ``state["pos"]`` is one position for every row (``[]``) or one per row
    (``[B]``): each row then takes its own RoPE angles, cache slot and
    attention length, and a row at a negative position is idle: its cache,
    recurrent state and position stay as they were (its logits are
    meaningless)."""
    P = cfg.scan_period()
    kinds = cfg.layer_kinds()[:P]
    pos = jnp.asarray(state["pos"])
    per_row = pos.ndim == 1
    x = (embeds if embeds is not None
         else embed_tokens(params, cfg, token[:, None]))   # [B,1,D]
    positions = pos[:, None] if per_row else pos[None]     # [B,1] or [1]
    angles = _angles_for(cfg, positions, None)

    def keep_idle(new, old):
        """Recurrent state of idle rows (negative ``pos``) left as it was."""
        if not per_row:
            return new
        live = pos >= 0
        return jax.tree.map(lambda n, o: jnp.where(
            live.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, old)

    def period(x, xs):
        pp, ps = xs
        new_states = []
        for i, kind in enumerate(kinds):
            p, st = pp[i], ps[i]
            y = apply_norm(p["norm1"], x, cfg.norm, cfg.norm_eps)
            mix = kind["mix"]
            if mix == "attn":
                o, st = _attn_decode(p["mix"], cfg, y, st, pos, angles)
                x = x + o
            elif mix == "mamba":
                o, new = mamba_decode_step(p["mix"], y, st, cfg.mamba_d_state)
                x, st = x + o, keep_idle(new, st)
            elif mix == "mlstm":
                o, new = mlstm_decode_step(p["mix"], y, st, cfg.n_heads)
                x, st = x + o, keep_idle(new, st)
            elif mix == "slstm":
                o, new = slstm_decode_step(p["mix"], y, st, cfg.n_heads)
                x, st = x + o, keep_idle(new, st)
            if kind["ff"] == "mlp":
                y2 = apply_norm(p["norm2"], x, cfg.norm, cfg.norm_eps)
                x = x + apply_mlp(p["ff"], y2, cfg.act)
            elif kind["ff"] == "moe":
                y2 = apply_norm(p["norm2"], x, cfg.norm, cfg.norm_eps)
                o, _ = apply_moe(p["ff"], y2, cfg.top_k, cfg.capacity_factor,
                                 cfg.act, dropless=True)
                x = x + o
            new_states.append(st)
        return x, tuple(new_states)

    x, new_blocks = jax.lax.scan(period, x,
                                 (params["period"], state["blocks"]))
    h = apply_norm(params["final_norm"], x[:, 0], cfg.norm, cfg.norm_eps)
    logits = (h @ lm_head(params, cfg)).astype(jnp.float32)
    new_pos = jnp.where(pos >= 0, pos + 1, pos) if per_row else pos + 1
    return logits, {"blocks": new_blocks, "pos": new_pos}


def prefill(params, cfg: ModelConfig, tokens, max_len: int, batch=None):
    """tokens [B,S] -> (last-token logits [B,V], decode state at pos=S)."""
    B, S = tokens.shape
    x = (batch["embeds"].astype(jnp.dtype(cfg.dtype))
         if batch and "embeds" in batch else embed_tokens(params, cfg, tokens))
    positions = jnp.arange(S)
    h, _, state_stacks = forward_hidden(params, cfg, x, positions, batch,
                                        collect_state=True, dropless=True)
    state = init_decode_state(cfg, B, max_len)
    T = _cache_len(cfg, max_len)
    P = cfg.scan_period()
    kinds = cfg.layer_kinds()[:P]
    new_blocks = []
    for i, st0 in enumerate(state["blocks"]):
        st = state_stacks[i]
        if kinds[i]["mix"] == "attn":
            k, v = st["k"], st["v"]                      # [n_periods,B,S,Hkv,dh]
            if S >= T:
                # rolling buffer: token j lives at slot j % T
                k = jnp.roll(k[:, :, S - T:], (S - T) % T, axis=2)
                v = jnp.roll(v[:, :, S - T:], (S - T) % T, axis=2)
                st = {"k": k, "v": v}
            else:
                st = {"k": st0["k"].at[:, :, :S].set(k),
                      "v": st0["v"].at[:, :, :S].set(v)}
        new_blocks.append(st)
    h_last = apply_norm(params["final_norm"], h[:, -1], cfg.norm, cfg.norm_eps)
    logits = (h_last @ lm_head(params, cfg)).astype(jnp.float32)
    return logits, {"blocks": tuple(new_blocks), "pos": jnp.int32(S)}
