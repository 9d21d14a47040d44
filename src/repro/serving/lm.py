"""LM adapter: the real model path behind the serving engine.

Wraps the framework's sharded prefill step
(:func:`repro.runtime.steps.build_prefill_step`) and round step
(:func:`~repro.runtime.steps.build_round_step`) into the adapter protocol
of :class:`repro.serving.engine.ServingEngine`.  The adapter owns one
decode cache of ``slots`` rows, allocated once.  A prefill runs batch 1
and hands its cache and first token off into the request's row
(``Request.slot``); one decode round then advances every row in one
program, reading the weights once for all of them, and its greedy tokens
stay on the device to feed the next round.  Device values stay
un-synchronised — XLA's async dispatch is the in-flight operation, and
the engine binds completion through ``tac.as_handle(tokens)`` (an
:class:`repro.core.tac.ArrayHandle`), so the event leg overlaps host
detokenisation with the next rounds.

A row holds every kind of state the model keeps: KV caches, and for the
Mamba2 hybrids the SSM states and conv tails beside them.  The
``serving.row_state_bytes`` gauges (label ``kind``: ``ssm``, ``kv``)
give one row's bytes of each.

``Request.prompt`` is an integer seed; the prompt tokens are drawn with
:func:`repro.models.inputs.make_batch` under that seed, which keeps the
two completion legs (and re-admissions after eviction) bit-identical.
"""

from __future__ import annotations

import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models import inputs as model_inputs
from ..models import model
from ..obs import trace as _tr
from ..obs.registry import REGISTRY
from ..runtime import steps
from .request import Request

__all__ = ["LMAdapter"]


class LMAdapter:
    """Greedy-decode adapter: batch-1 prefill, one round over all rows."""

    def __init__(self, cfg: Any, mesh: Any, policy: Any, params: Any, *,
                 prompt_len: int, gen_len: int, slots: int = 4) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.policy = policy
        self.params = params
        self.prompt_len = prompt_len
        self.total_len = prompt_len + gen_len
        self.slots = slots
        #: the last round's logits, (slots, vocab)
        self.logits: Optional[jax.Array] = None
        with mesh:
            batch = model_inputs.make_batch(
                cfg, batch=1, seq=prompt_len, kind="prefill",
                key=jax.random.PRNGKey(0))
            self._prefill = steps.build_prefill_step(
                cfg, mesh, policy,
                abstract_batch=jax.eval_shape(lambda: batch))
            self._decode, self._handoff, a_cache = steps.build_round_step(
                cfg, mesh, policy, slots=slots, cache_len=self.total_len)
            self._cache = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), a_cache)
            self._toks = jnp.zeros((slots,), jnp.int32)
        #: one row's bytes of state, by kind: ``kv`` (grows with the
        #: cache length) and ``ssm`` (recurrent state of a fixed size)
        self.row_state_bytes = {"ssm": 0, "kv": 0}
        for path, a in jax.tree_util.tree_flatten_with_path(a_cache)[0]:
            kind = "kv" if model.is_kv(path) else "ssm"
            self.row_state_bytes[kind] += a.size // slots * a.dtype.itemsize
        self._publish_row_state()

    def _publish_row_state(self) -> None:
        """Set the ``serving.row_state_bytes`` gauges; again at every
        handoff, so that a reset of the registry does not lose them."""
        for kind, n in self.row_state_bytes.items():
            REGISTRY.gauge("serving.row_state_bytes", kind=kind).set(n)

    # -- the adapter protocol -----------------------------------------------
    def warmup(self) -> None:
        """Compile prefill, the handoff and the round step."""
        req = Request(rid=-1, prompt=0, gen_len=2)
        req.slot = 0
        self.prefill(req)
        jax.block_until_ready(
            self.decode_round([1] + [None] * (self.slots - 1)))

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

    def prefill(self, req: Request) -> Any:
        """Prefill ``req`` into its row; returns its first token, (1,)."""
        with self.mesh:
            logits, cache = self._prefill(self.params, self.prompt(req))
            t0 = time.monotonic() if _tr.TRACING else 0.0
            self._cache, self._toks, first = self._handoff(
                self._cache, self._toks, cache, logits, req.slot)
            if _tr.TRACING:
                _tr.TRACER.span("serving", "pad_cache", t0, time.monotonic(),
                                rid=req.rid,
                                ssm_bytes=self.row_state_bytes["ssm"],
                                kv_bytes=self.row_state_bytes["kv"])
        self._publish_row_state()
        return first

    def decode_round(self, row_steps: Sequence[Optional[int]]) -> Any:
        """Advance every row one token: row r decodes step ``row_steps[r]``
        (None: an empty row, computed and ignored).  Returns the rows'
        tokens, (slots,)."""
        pos = np.array([0 if s is None else self.prompt_len + s - 1
                        for s in row_steps], np.int32)
        with self.mesh:
            self._toks, self.logits, self._cache = self._decode(
                self.params, self._cache, self._toks, pos)
        return self._toks

    def detok(self, req: Request, step: int, tok: Any) -> int:
        return int(tok)
