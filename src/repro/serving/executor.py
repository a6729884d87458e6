"""Model executors: per-request token production for the serving engine.

The engine's step loop is model-agnostic — it asks an executor for the
next chunk of tokens' K/V bytes (to mirror into the cold paged pool) and
the next output token, per request. Each step it first hands the executor
every decoding request at once (``decode_batch``), then asks for each
request's result (``decode``). Two implementations:

* :class:`ModelExecutor` — the real thing. It owns one decode row per
  engine slot (``set_geometry``) and decodes every decoding row in one
  batched ``decode_step`` call per engine step. **Chunked prefill** feeds
  prompt tokens through the same jitted program at batch 1, on a state of
  the rows' length; so a long prompt costs ``ceil(prompt/prefill_chunk)``
  engine steps instead of stalling in-flight decodes for a monolithic
  prefill. The chunk that consumes the last prompt token emits the first
  output token (greedy argmax) and copies the request's state into its
  slot's row — token positions, cache slots and logits line up exactly
  with the one-shot ``model.prefill`` (pinned at the 5e-3 model tolerance
  in ``tests/test_serving.py``).
* :class:`SyntheticExecutor` — no model: deterministic PRNG K/V keyed by
  ``(request id, position)`` and counter tokens, drawn per request (its
  ``decode_batch`` stages nothing). The tiered data path, paging and the
  §6.4 pin are all still real; scheduling benchmarks use this to sweep
  arrival × load without paying model compute.

Both produce K/V bytes deterministic per (request, position) so the
flat/tiered equivalence pin is meaningful under any chunking or slot
assignment. ``decode(req)`` with nothing staged runs the request alone
(the batch-1 path).
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


@jax.jit
def _greedy_rows(logits, k, v):
    """Each row's greedy token ``[S]``, and its K and V ``[Hkv, dh]`` as
    arrays of their own, in one program."""
    return jnp.argmax(logits, -1).astype(jnp.int32), tuple(k), tuple(v)


class SyntheticExecutor:
    """PRNG K/V + counter tokens; the data path without the model."""

    def __init__(self, n_kv_heads: int, head_dim: int, dtype="float32",
                 seed: int = 0):
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.dtype = jnp.dtype(dtype).name
        self._key = jax.random.PRNGKey(seed)

    def set_geometry(self, slots: int, max_len: int) -> None:
        """Nothing to shape: each request's K/V are drawn on their own."""

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

    def decode_batch(self, reqs: list[Request]) -> None:
        """Stages nothing: :meth:`decode` draws each request's K/V."""

    def decode(self, req: Request):
        """K/V of the token being consumed (position ``length - 1``) and
        the next output token."""
        pos = req.prefilled + req.decoded - 1
        k, v = self._kv(req, pos, 1)
        return k[0], v[0], (req.req_id + req.decoded) % 251


class ModelExecutor:
    """Real model: one decode row per engine slot, batched decode, chunked
    prefill at batch 1."""

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
        #: batch-1 decode states of the requests not (yet) in a row
        self._states: dict[int, dict] = {}
        #: decode rows ``[slots, max_len]`` (``set_geometry``), blocks only:
        #: each call's positions come from the host
        self._rows: tuple | None = None
        self._slots = 0
        self._max_len: int | None = None
        self._staged: dict[int, tuple] = {}
        self._prompts: dict[int, jax.Array] = {}
        self._last_tok: dict[int, int] = {}
        #: logits of each request's newest batch-1 token (prefill's last
        #: chunk: the first output token's)
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

    def set_geometry(self, slots: int, max_len: int) -> None:
        """One decode row per engine slot, each ``max_len`` long (the
        longest context the engine's pages hold); every request's state is
        then that long, so one batch-1 and one batched shape serve every
        request length. Runs each of those programs once, so they are
        compiled before the first request: the row copy (zeros over
        zeros), a batch-1 step, and a batched step with every row idle,
        which leaves the rows as they were."""
        self._rows = self.model.init_decode_state(slots, max_len)["blocks"]
        self._slots, self._max_len = slots, max_len
        one = self.model.init_decode_state(1, max_len)
        self._rows = _put_row(self._rows, one["blocks"], 0)
        self._decode(self.params, jnp.zeros((1,), jnp.int32), one)
        self.decode_batch([])

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
        in_row = self._in_row(req)
        if in_row and req.max_len > self._max_len:
            raise ValueError(
                f"request {req.req_id}: max_len {req.max_len} exceeds the "
                f"decode rows ({self._max_len})")
        self.prompt_tokens(req)
        self._states[req.req_id] = self.model.init_decode_state(
            1, self._max_len if in_row else req.max_len)

    def _in_row(self, req: Request) -> bool:
        """Whether ``req`` decodes in a row: it holds one of the slots the
        rows were shaped for (a request outside the engine, such as a
        warm-up's, stays at batch 1 and its own length)."""
        return 0 <= req.slot < self._slots

    def end(self, req: Request) -> None:
        self._states.pop(req.req_id, None)
        self._staged.pop(req.req_id, None)
        self._prompts.pop(req.req_id, None)
        self._last_tok.pop(req.req_id, None)
        self.last_logits.pop(req.req_id, None)

    def _decode_and_kv(self, params, tok, state):
        """``decode_step`` plus the K/V bytes it just wrote at each input
        token's cache position — ``[Hkv, dh]`` each for a batch-1 state,
        ``[B, Hkv, dh]`` for one with a position per row — from the first
        attention stack of the scan period (``None`` for cache-free
        families). One jitted program: no per-position host work."""
        pos = state["pos"]
        logits, state = self.model.decode_step(params, tok, state)
        if self._attn_period is None:
            return logits, state, None, None
        blk = state["blocks"][self._attn_period]
        if pos.ndim:
            rows = jnp.arange(pos.shape[0])
            k, v = (blk[n][0, rows, jnp.maximum(pos, 0)] for n in ("k", "v"))
            return logits, state, k, v
        k, v = (jax.lax.dynamic_index_in_dim(blk[n][0, 0], pos,
                                             keepdims=False)
                for n in ("k", "v"))
        return logits, state, k, v

    def _feed(self, req: Request, token: int):
        """One batch-1 ``decode_step``: returns ``(logits [V], k, v)`` where
        k/v are the bytes written for the *input* token at its position.
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
        output token when the prompt is exhausted, when the request's state
        also moves into its slot's decode row."""
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
            if self._in_row(req):
                self._rows = _put_row(self._rows,
                                      self._states.pop(req.req_id)["blocks"],
                                      req.slot)
        return jnp.stack(ks), jnp.stack(vs), tok

    def decode_batch(self, reqs: list[Request]) -> None:
        """One batched ``decode_step`` over the decode rows: every request
        in ``reqs`` (each in its slot's row) consumes its last emitted
        token; the other rows sit idle at position -1. Stages each
        request's :meth:`decode` result; one device→host read of the
        greedy tokens."""
        # a request without a row (outside the engine's slots) decodes
        # alone, in decode()
        reqs = [r for r in reqs if r.req_id not in self._states]
        if not self._slots:
            return
        tok = np.zeros((self._slots,), np.int32)
        pos = np.full((self._slots,), -1, np.int32)
        for r in reqs:
            tok[r.slot] = self._last_tok[r.req_id]
            pos[r.slot] = r.prefilled + r.decoded - 1
        logits, state, k, v = self._decode(
            self.params, jnp.asarray(tok),
            {"blocks": self._rows, "pos": jnp.asarray(pos)})
        self._rows = state["blocks"]
        if k is None:                    # cache-free: synthetic bytes
            kv = [self._synth.decode(r)[:2] for r in reqs]
            toks = np.asarray(jnp.argmax(logits, -1))
        else:
            toks, ks, vs = _greedy_rows(logits, k, v)
            kv = [(ks[r.slot], vs[r.slot]) for r in reqs]
            toks = np.asarray(toks)
        for r, (kr, vr) in zip(reqs, kv):
            t = int(toks[r.slot])
            self._last_tok[r.req_id] = t
            self._staged[r.req_id] = (kr, vr, t)

    def decode(self, req: Request):
        """Consume the last emitted token, emit the next one: the result
        :meth:`decode_batch` staged, else the request decoded alone (its
        batch-1 state, or its row)."""
        if req.req_id not in self._staged and req.req_id not in self._states:
            self.decode_batch([req])
        if req.req_id in self._staged:
            return self._staged.pop(req.req_id)
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


@functools.partial(jax.jit, donate_argnums=0)
def _put_row(rows, one, row):
    """Batch-1 decode blocks ``one`` written into row ``row`` of ``rows``
    (leaves ``[n_periods, B, ...]``)."""
    return jax.tree.map(
        lambda r, o: jax.lax.dynamic_update_slice_in_dim(r, o, row, axis=1),
        rows, one)
