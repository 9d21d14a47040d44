"""LM adapter: the real model path behind the serving engine.

Wraps the framework's sharded prefill/decode steps
(:func:`repro.runtime.steps.build_prefill_step` /
:func:`~repro.runtime.steps.build_decode_step`) into the adapter
protocol of :class:`repro.serving.engine.ServingEngine`: each request
runs batch-1 greedy decoding with its own fixed-size KV cache, handed
off from prefill via :func:`repro.models.model.pad_cache`.  Device
values stay un-synchronised — XLA's async dispatch is the in-flight
operation, and the engine binds completion through
``tac.as_handle(token)`` (an :class:`repro.core.tac.ArrayHandle`), so
the event leg overlaps host detokenisation with the next decode steps.

``Request.prompt`` is an integer seed; the prompt tokens are drawn with
:func:`repro.models.inputs.make_batch` under that seed, which keeps the
two completion legs (and re-admissions after eviction) bit-identical.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import model, inputs as model_inputs
from ..runtime import steps
from .request import Request

__all__ = ["LMAdapter"]


class LMAdapter:
    """Batch-1 greedy-decode adapter over the sharded step functions."""

    def __init__(self, cfg: Any, mesh: Any, policy: Any, params: Any, *,
                 prompt_len: int, gen_len: int) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.policy = policy
        self.params = params
        self.prompt_len = prompt_len
        self.total_len = prompt_len + gen_len
        with mesh:
            batch = model_inputs.make_batch(
                cfg, batch=1, seq=prompt_len, kind="prefill",
                key=jax.random.PRNGKey(0))
            self._prefill = steps.build_prefill_step(
                cfg, mesh, policy,
                abstract_batch=jax.eval_shape(lambda: batch))
            dec_spec = jax.eval_shape(
                lambda: {"tokens": jnp.zeros((1, 1), jnp.int32)})
            self._decode, _ = steps.build_decode_step(
                cfg, mesh, policy, batch=1, cache_len=self.total_len,
                abstract_batch=dec_spec, donate=False)

    # -- the adapter protocol -----------------------------------------------
    def warmup(self) -> None:
        """Compile prefill + decode outside any timed region."""
        req = Request(rid=-1, prompt=0, gen_len=2)
        tok, state = self.prefill(req)
        tok, _ = self.decode(req, state, 1)
        jax.block_until_ready(tok)

    def prompt(self, req: Request) -> Any:
        """The prompt batch drawn from ``req.prompt`` as a seed."""
        return model_inputs.make_batch(
            self.cfg, batch=1, seq=self.prompt_len, kind="prefill",
            key=jax.random.PRNGKey(int(req.prompt)))

    def compiled_prefill(self, req: Request) -> Any:
        """The compiled prefill program (``as_text()`` shows whether a
        Pallas kernel, ``tpu_custom_call``, is on the path)."""
        with self.mesh:
            return self._prefill.lower(self.params,
                                       self.prompt(req)).compile()

    def prefill_logits(self, req: Request) -> Any:
        """Last-position prefill logits, shape (1, 1, vocab)."""
        with self.mesh:
            logits, _ = self._prefill(self.params, self.prompt(req))
        return logits

    def prefill(self, req: Request) -> Tuple[Any, Any]:
        with self.mesh:
            logits, cache = self._prefill(self.params, self.prompt(req))
            cache = model.pad_cache(self.cfg, cache, self.total_len)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return tok, (cache, tok)

    def decode(self, req: Request, state: Any,
               step: int) -> Tuple[Any, Any]:
        cache, prev = state
        with self.mesh:
            logits, cache = self._decode(
                self.params, cache, {"tokens": prev[:, None]},
                jnp.int32(self.prompt_len + step - 1))
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return tok, (cache, tok)

    def detok(self, req: Request, step: int, tok: Any) -> int:
        return int(np.asarray(tok)[0])
