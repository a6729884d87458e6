"""Model executors: per-request token production for the serving engine.

The engine's step loop is model-agnostic — it asks an executor for the
next chunk of tokens' K/V bytes (to mirror into the cold paged pool) and
the next output token, per request. Two implementations:

* :class:`ModelExecutor` — the real thing. Each request owns a batch-1
  decode state; **chunked prefill** feeds prompt tokens through the same
  jitted ``decode_step`` the decode path uses (one compile serves every
  request and both phases), so a long prompt costs
  ``ceil(prompt/prefill_chunk)`` engine steps instead of stalling
  in-flight decodes for a monolithic prefill. The chunk that consumes the
  last prompt token emits the first output token (greedy argmax) — token
  positions, cache slots and logits line up exactly with the one-shot
  ``model.prefill`` (pinned at the 5e-3 model tolerance in
  ``tests/test_serving.py``).
* :class:`SyntheticExecutor` — no model: deterministic PRNG K/V keyed by
  ``(request id, position)`` and counter tokens. The tiered data path,
  paging and the §6.4 pin are all still real; scheduling benchmarks use
  this to sweep arrival × load without paying model compute.

Both produce K/V bytes deterministic per (request, position) so the
flat/tiered equivalence pin is meaningful under any chunking or slot
assignment.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import build_model

from .request import Request


@functools.partial(jax.jit, static_argnames=("n", "hkv", "dh", "dtype"))
def _synth_kv(key, req_id, start, n: int, hkv: int, dh: int, dtype: str):
    """Deterministic per-(request, position) K/V page bytes, ``[n,Hkv,dh]``."""
    def one(pos):
        kk = jax.random.fold_in(jax.random.fold_in(key, req_id), pos)
        kv = jax.random.normal(kk, (2, hkv, dh), jnp.dtype(dtype))
        return kv[0], kv[1]

    return jax.vmap(one)(start + jnp.arange(n, dtype=jnp.int32))


class SyntheticExecutor:
    """PRNG K/V + counter tokens; the data path without the model."""

    def __init__(self, n_kv_heads: int, head_dim: int, dtype="float32",
                 seed: int = 0):
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.dtype = jnp.dtype(dtype).name
        self._key = jax.random.PRNGKey(seed)

    def begin(self, req: Request) -> None:
        pass

    def end(self, req: Request) -> None:
        pass

    def _kv(self, req: Request, start: int, n: int):
        return _synth_kv(self._key, req.req_id, start, n,
                         self.n_kv_heads, self.head_dim, self.dtype)

    def prefill_chunk(self, req: Request, n: int):
        """K/V for prompt positions ``[prefilled, prefilled+n)`` and, when
        the chunk finishes the prompt, the first output token."""
        k, v = self._kv(req, req.prefilled, n)
        done = req.prefilled + n >= req.prompt_len
        tok = req.req_id % 251 if done else None
        return k, v, tok

    def decode(self, req: Request):
        """K/V of the token being consumed (position ``length - 1``) and
        the next output token."""
        pos = req.prefilled + req.decoded - 1
        k, v = self._kv(req, pos, 1)
        return k[0], v[0], (req.req_id + req.decoded) % 251


class ModelExecutor:
    """Real model, batch-1 per-request decode states, chunked prefill."""

    def __init__(self, cfg, seed: int = 0):
        if cfg.family == "encdec":
            raise ValueError("continuous-batching engine drives decoder-only "
                             "families; encdec serving stays on the batch "
                             "driver")
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = jax.jit(lambda k: self.model.init_params(k)[0])(
            jax.random.PRNGKey(seed))
        self._decode = jax.jit(self._decode_and_kv)
        self._prefill = jax.jit(self.model.prefill, static_argnums=2)
        self._key = jax.random.PRNGKey(seed + 1)
        self._states: dict[int, dict] = {}
        self._prompts: dict[int, jax.Array] = {}
        self._last_tok: dict[int, int] = {}
        self.last_logits: dict[int, jax.Array] = {}
        self.n_q_heads = cfg.n_heads
        self.n_kv_heads = cfg.n_kv_heads
        self.head_dim = cfg.head_dim
        self.dtype = jnp.dtype(cfg.dtype).name
        # a rolling SWA cache would overwrite mirrored positions; the paged
        # mirror needs the full context resident (checked per request in
        # begin())
        self._cache_cap = cfg.sliding_window or None
        self._synth = SyntheticExecutor(cfg.n_kv_heads, cfg.head_dim,
                                        cfg.dtype, seed=seed + 2)
        self._attn_period = next(
            (i for i, kind in enumerate(cfg.layer_kinds()[:cfg.scan_period()])
             if kind["mix"] == "attn"), None)

    def prompt_tokens(self, req: Request) -> jax.Array:
        if req.req_id not in self._prompts:
            key = jax.random.fold_in(self._key, req.req_id)
            self._prompts[req.req_id] = jax.random.randint(
                key, (req.prompt_len,), 0, self.cfg.vocab_size, jnp.int32)
        return self._prompts[req.req_id]

    def begin(self, req: Request) -> None:
        if self._cache_cap is not None and req.max_len > self._cache_cap:
            raise ValueError(
                f"request {req.req_id}: max_len {req.max_len} exceeds the "
                f"sliding-window cache ({self._cache_cap}) — the paged "
                "mirror would lose overwritten positions")
        self.prompt_tokens(req)
        self._states[req.req_id] = self.model.init_decode_state(
            1, req.max_len)

    def end(self, req: Request) -> None:
        self._states.pop(req.req_id, None)
        self._prompts.pop(req.req_id, None)
        self._last_tok.pop(req.req_id, None)
        self.last_logits.pop(req.req_id, None)

    def _decode_and_kv(self, params, tok, state):
        """``decode_step`` plus the K/V bytes it just wrote at the input
        token's cache position — ``[Hkv, dh]`` each — from the first
        attention stack of the scan period (``None`` for cache-free
        families). One jitted program: no per-position host work."""
        pos = state["pos"]
        logits, state = self.model.decode_step(params, tok, state)
        if self._attn_period is None:
            return logits, state, None, None
        blk = state["blocks"][self._attn_period]
        k, v = (jax.lax.dynamic_index_in_dim(blk[n][0, 0], pos,
                                             keepdims=False)
                for n in ("k", "v"))
        return logits, state, k, v

    def _feed(self, req: Request, token: int):
        """One ``decode_step``: returns ``(logits [V], k, v)`` where k/v
        are the bytes written for the *input* token at its position.
        Cache-free families (pure mamba/xlstm) mirror synthetic bytes so
        the data path stays end-to-end real."""
        tok = jnp.asarray([token], jnp.int32)
        logits, state, k, v = self._decode(self.params, tok,
                                           self._states[req.req_id])
        self._states[req.req_id] = state
        if k is None:
            k, v = self._synth._kv(req, int(state["pos"]) - 1, 1)
            k, v = k[0], v[0]
        return logits[0], k, v

    def prefill_chunk(self, req: Request, n: int):
        """Consume ``n`` prompt tokens; K/V ``[n, Hkv, dh]``; the first
        output token when the prompt is exhausted."""
        prompt = np.asarray(self.prompt_tokens(req))
        ks, vs = [], []
        logits = None
        for j in range(req.prefilled, req.prefilled + n):
            logits, k, v = self._feed(req, int(prompt[j]))
            ks.append(k)
            vs.append(v)
        tok = None
        if req.prefilled + n >= req.prompt_len:
            tok = int(jnp.argmax(logits))
            self._last_tok[req.req_id] = tok
            self.last_logits[req.req_id] = logits
        return jnp.stack(ks), jnp.stack(vs), tok

    def decode(self, req: Request):
        """Consume the last emitted token, emit the next one."""
        logits, k, v = self._feed(req, self._last_tok[req.req_id])
        tok = int(jnp.argmax(logits))
        self._last_tok[req.req_id] = tok
        self.last_logits[req.req_id] = logits
        return k, v, tok

    def oneshot_prefill_logits(self, req: Request) -> jax.Array:
        """Reference: ``model.prefill`` over the same prompt in one shot
        (the chunked-prefill equivalence oracle; [V] float32)."""
        batch = {"tokens": self.prompt_tokens(req)[None]}
        logits, _ = self._prefill(self.params, batch, req.max_len)
        return logits[0]
