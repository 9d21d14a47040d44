"""Synthetic model adapter: async device queue, deterministic tokens.

The bench/test stand-in for the LM adapter
(:class:`repro.serving.lm.LMAdapter`), with the same protocol: a prefill
into the request's row and one decode round over every row.  Device work
runs in order on a **device queue** — one thread standing in for the
accelerator's stream — and returns :class:`concurrent.futures.Future`\\ s,
so the engine's handles are push-capable
(:class:`repro.core.tac.FutureHandle` → the continuation engine is
notified at completion, zero polls) and the device latency is genuinely
asynchronous: it costs wall-clock but no CPU, exactly like a kernel
executing on an accelerator while the host runs tasks.  A dispatch never
waits for earlier device work: the queue orders it.

That asymmetry is what separates the two completion legs: the
blocking-sentinel leg holds the device chain through every device wait
and host detokenisation; the event-bound leg frees it at dispatch
(``tac.iwait``), so host work overlaps the next rounds.  Host
detokenisation is sha256 work (GIL-releasing, cache-resident).

Tokens are a pure function of ``(prompt seed, step)`` — the two legs
must emit bit-identical streams, and an evicted request re-generates
the same tokens after re-admission.
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import time
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .request import Request

__all__ = ["SyntheticAdapter", "token_at"]


def token_at(seed: int, step: int) -> int:
    """The deterministic token stream: pure in (seed, step)."""
    return (seed + 31 * step + 7) % 997


class SyntheticAdapter:
    """Deterministic adapter with tunable device/host cost.

    ``dev_ms`` is the device latency of one prefill or one decode round
    (slept on the device-queue thread — off-CPU, like an accelerator);
    ``dim`` sizes the real jitted computation dispatched with it;
    ``host_rounds`` sizes the sha256 host work of one detokenisation;
    ``slots`` is the number of rows a round decodes.
    ``request.prompt`` is the integer seed.
    """

    def __init__(self, *, dev_ms: float = 4.0, host_rounds: int = 8,
                 dim: int = 64, slots: int = 4) -> None:
        self.dev_ms = dev_ms
        self.host_rounds = host_rounds
        self.dim = dim
        self.slots = slots
        self._queue = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="synth-device")
        w = np.linalg.qr(np.random.default_rng(0)
                         .standard_normal((dim, dim)))[0]
        self._w = jnp.asarray(w, jnp.float32)
        self._seeds = np.zeros(slots, np.int32)     # host, set at prefill
        self._vec = jnp.zeros((slots, dim), jnp.float32)  # device thread's

        def _step(vec: jax.Array, seeds: jax.Array,
                  steps: jax.Array) -> Tuple[jax.Array, jax.Array]:
            vec = jnp.tanh(vec @ self._w)
            tok = (seeds + 31 * steps + 7) % 997
            return tok.astype(jnp.int32), vec

        self._step = jax.jit(_step)
        self._host_buf = bytes(range(256)) * 256    # 64 KiB, L2-resident

    def _device(self, seeds: np.ndarray, steps: np.ndarray) -> np.ndarray:
        """Device work, in queue order: latency + computation."""
        time.sleep(self.dev_ms * 1e-3)      # accelerator time, off-CPU
        tok, self._vec = self._step(self._vec, jnp.asarray(seeds),
                                    jnp.asarray(steps))
        return np.asarray(tok)              # future done == value ready

    # -- the adapter protocol -----------------------------------------------
    def warmup(self) -> None:
        """Compile the step function (outside any timed region)."""
        for n in (1, self.slots):
            z = jnp.zeros((n,), jnp.int32)
            self._step(self._vec, z, z)[0].block_until_ready()

    def prefill(self, req: Request) -> Any:
        """Dispatch the prompt pass into the request's row; returns the
        first-token future."""
        seed = int(req.prompt)
        self._seeds[req.slot] = seed
        return self._queue.submit(self._device, np.array([seed], np.int32),
                                  np.zeros(1, np.int32))

    def decode_round(self, row_steps: Sequence[Optional[int]]) -> Any:
        """Dispatch one decode round over every row (None: an empty row);
        returns the future of the rows' tokens."""
        steps = np.array([0 if s is None else s for s in row_steps],
                         np.int32)
        return self._queue.submit(self._device, self._seeds.copy(), steps)

    def detok(self, req: Request, step: int, tok: Any) -> int:
        """Host detokenisation: sha256 host work + the token value."""
        h = hashlib.sha256()
        for _ in range(self.host_rounds):
            h.update(self._host_buf)
        assert h.digest()
        return int(tok)

    def close(self) -> None:
        self._queue.shutdown(wait=False)

    def __enter__(self) -> "SyntheticAdapter":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
