"""What decides ``correct``: the served tokens against the float32 reference.

Three numbers, each with its limit:

* ``unfinished``: requests sent and not taken back that never finished
  (limit 0); a run whose engine raised or hung counts one more.
* ``emission_faults``: finished requests whose tokens were not emitted
  exactly once each, in step order (limit 0).  This covers the engine and
  the task runtime's emission path.
* ``logit_gap``: for a sample of finished requests drawn from the seed,
  the longest among them, at least the mix's ``min_requests`` and
  ``min_tokens``, the reference runs once over each prompt followed by its
  served tokens; at each position that produced a served
  token, the gap is the reference's largest logit minus its logit of the
  served token.  The number is the widest gap.  Greedy decoding serves the
  program's own argmax, so a sound program serves tokens the reference
  ranks first or within rounding of first; a wrong token, a stale cache or
  a lower precision opens the gap.  This covers the Pallas prefill, the
  cache handoff, decode through the cache at the timed cache length, and
  the emitted values.  Its limit is the cell's, set from the readings in
  PERF.md.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List

import numpy as np

# The gap reported when no request finished, so nothing could be compared.
NOT_COMPARED = 1e30


def emission_faults(served: Dict[int, List], states: Dict[int, Any],
                    gen: Dict[int, int]) -> List[int]:
    """Finished requests whose (step, token) list is not steps 0..G-1,
    each once, in emission order."""
    bad = []
    for rid, toks in served.items():
        if getattr(states[rid], "name", states[rid]) != "DONE":
            continue
        if [s for s, _ in toks] != list(range(gen[rid])):
            bad.append(rid)
    return bad


def sample(served: Dict[int, List], ok: List[int], *, seed: int,
           min_tokens: int, min_requests: int,
           max_requests: int) -> List[int]:
    """The longest finished request, then others drawn from the seed until
    there are ``min_requests`` requests and ``min_tokens`` served tokens,
    or ``max_requests`` requests."""
    if not ok:
        return []
    longest = max(ok, key=lambda r: (len(served[r]), -r))
    rest = [r for r in sorted(ok) if r != longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 11]))
    picked = [longest]
    total = len(served[longest])
    for r in rng.permutation(rest) if rest else []:
        if (total >= min_tokens and len(picked) >= min_requests) \
                or len(picked) >= max_requests:
            break
        picked.append(int(r))
        total += len(served[int(r)])
    return picked


def inputs(prompts: np.ndarray, served: Dict[int, List], rids: List[int]):
    """Each request's prompt and served tokens, less the last, and the
    positions whose next-token logits chose the served tokens."""
    seqs, positions, tokens = [], [], []
    for rid in rids:
        toks = np.array([v for _, v in served[rid]], np.int32)
        p = prompts[rid]
        seqs.append(np.concatenate([p, toks[:-1]]))
        positions.append(np.arange(len(p) - 1, len(p) - 1 + len(toks)))
        tokens.append(toks)
    return seqs, positions, tokens


def gaps(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """The reference's best logit minus its logit of each token."""
    return logits.max(-1) - np.take_along_axis(logits, tokens[:, None],
                                               -1)[:, 0]


def run_checks(*, cell, ref, wkey, prompts, served, states, gen, withdrawn,
               seed: int, log: Callable[[str], None],
               engine_failed: bool) -> Dict[str, Dict[str, float]]:
    unfinished = sum(1 for rid, st in states.items() if rid not in withdrawn
                     and getattr(st, "name", st) != "DONE")
    bad = emission_faults(served, states, gen)
    ok = [rid for rid, st in states.items()
          if getattr(st, "name", st) == "DONE" and rid not in bad]
    pol = cell.mix["check"]
    rids = sample(served, ok, seed=seed, **pol)
    widest = NOT_COMPARED
    if rids:
        t = time.monotonic()
        seqs, positions, tokens = inputs(prompts, served, rids)
        logits = ref.forward(cell.mc, wkey, seqs, positions,
                             shape=ref_shape(cell.mix))
        per = [gaps(lg, tk) for lg, tk in zip(logits, tokens)]
        widest = float(max(g.max() for g in per))
        log(f"[bench] reference over {len(rids)} requests, "
            f"{sum(len(t) for t in tokens)} served tokens, longest "
            f"{max(len(t) for t in tokens)}; gaps > 0 at "
            f"{sum(int((g > 0).sum()) for g in per)} positions; "
            f"{time.monotonic() - t:.1f} s")
    limit = float(cell.params["limits"]["logit_gap"])
    return {
        "unfinished": {"value": unfinished + int(engine_failed), "limit": 0},
        "emission_faults": {"value": len(bad), "limit": 0},
        "logit_gap": {"value": widest, "limit": limit},
    }


def ref_shape(mix) -> tuple:
    """(rows, length, positions) the reference pads a check to: one shape
    per mix, so that it compiles once and its program is cached."""
    out = mix["output"]["max"]
    return (mix["check"]["max_requests"], mix["prompt_len"] + out - 1, out)


def control_gaps(ref, mc, wkey, seqs, positions, ref_logits,
                 shape=(0, 0, 0)) -> np.ndarray:
    """The control: the reference computed with every weight matmul in
    float8 (one precision step below the configuration's bfloat16), put in
    the program's place.  At each position, the float32 reference's gap of
    the token the control ranks first."""
    low = ref.forward(mc, wkey, seqs, positions, quant=True, shape=shape)
    return np.concatenate([gaps(lg, lo.argmax(-1).astype(np.int32))
                           for lg, lo in zip(ref_logits, low)])
