"""Functional JAX model zoo for the assigned architectures.

Pure-functional models (pytree params, no NN library): dense / MoE / hybrid
Mamba / xLSTM decoder LMs, plus one encoder-decoder. Every model exposes the
same step surface consumed by ``repro.launch``:

* ``init_params(rng, cfg)``  -> (params, param_specs)
* ``train_forward(params, cfg, batch)`` -> scalar loss
* ``prefill(params, cfg, tokens, ...)`` -> (logits_last, kv_state)
* ``decode_step(params, cfg, token, state)`` -> (logits, state); the
  position lives in ``state["pos"]``: ``[]`` for every row, or ``[B]`` for
  one per row (a negative one leaves its row idle)
"""

from .config import ModelConfig
# build_model imported lazily in model.py (late in the build)

__all__ = ["ModelConfig"]
