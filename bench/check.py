"""Is what the timed path served correct? A comparison with the plain
reference.

Once the window has closed, a sample of the requests the window finished
is drawn from the seed: the longest always, and others until the sample
holds both ``check_requests`` requests and ``check_tokens`` served tokens
(``bench/cells/<cell>.json``), so that it spans several slots. The
reference (``bench/references/<name>.py``, named by the configuration)
runs once over each prompt followed by its served tokens, on weights it
draws itself from the seed. At every served position it reads how far the
served token's logit lies below its own best logit there. Greedy tokens
of a sound program lie at the reference's best, or a rounding's width
below it where two candidates nearly tie. The number compared with the
cell's limit is

* ``gap_max``: the widest such gap over every served token of the sample,
  in logits.

With ``controls``, the run also reports what the limit is set from: the
same number for the control (the reference at float8: at each position
the token it puts first, ``control_gap_max``) and for a token altered
where it is produced (every served token id plus one, ``altered_gap_max``).
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def finished(win) -> list:
    """Requests in flight in the window that have finished (at the close,
    or after it while the harness served on without new submissions)."""
    return [r for r in win.recs if r.outcome == "completed"
            and r.done is not None and r.done >= win.t0
            and r.submitted < win.t1]


def pick(win, seed: int, shape: dict) -> list:
    """The window's finished requests to compare, longest first, until the
    sample holds both the cell's requests and its served tokens."""
    done = finished(win)
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.ticket.tokens), r.item.index))
    rest = done[1:]
    order = np.random.default_rng([int(seed) % (1 << 63), 7]).permutation(
        len(rest))
    out, tokens = [done[0]], len(done[0].ticket.tokens)
    for i in order:
        if (tokens >= shape["check_tokens"]
                and len(out) >= shape["check_requests"]):
            break
        out.append(rest[i])
        tokens += len(rest[i].ticket.tokens)
    return out


def _gaps(lg: np.ndarray, toks: np.ndarray) -> np.ndarray:
    """Per position: how far the token's logit lies below the best."""
    return lg.max(-1) - lg[np.arange(len(toks)), toks]


def readings(cell, s32: int, served: List[Tuple[np.ndarray, np.ndarray]],
             controls: bool = False) -> dict:
    """The number compared (and, with ``controls``, the control's and the
    altered tokens'), over every served token of the sample."""
    from bench import weights
    from bench.harness import BENCH, load_module

    config = cell.config
    vocab = config["vocab_size"]
    ref = load_module(BENCH / "references" / f"{config['reference']}.py",
                      config["reference"])
    w = weights.make(config, s32)
    got = {"served": [], "control": [], "altered": []}
    argmax = []
    for prompt, toks in served:
        toks = np.asarray(toks, np.int64)
        if len(toks) == 0:
            continue
        if toks.min() < 0 or toks.max() >= vocab:
            got["served"].append(np.array([np.inf]))
            continue
        seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
        lg = ref.logits(w, config, seq, len(prompt) - 1)
        got["served"].append(_gaps(lg, toks))
        argmax.append(lg.argmax(-1) == toks)
        if controls:
            lo = ref.logits(w, config, seq, len(prompt) - 1, low=True)
            got["control"].append(_gaps(lg, lo.argmax(-1)))
            got["altered"].append(_gaps(lg, (toks + 1) % vocab))
    out = {}
    for k, v in got.items():
        if v:
            g = np.concatenate(v)
            pre = "" if k == "served" else f"{k}_"
            out[pre + "gap_max"] = float(g.max())
            out[pre + "gap_mean"] = float(g.mean())
    if argmax:
        out["argmax_share"] = float(np.concatenate(argmax).mean())
    return out


def run(cell, s32: int, served, readings_too: bool = False):
    """(checks, correct, extra): each compared number beside its limit,
    whether all are within them, and the control readings if asked."""
    got = readings(cell, s32, served, controls=readings_too)
    limits = cell.shape["limits"]
    checks = {}
    for name, limit in limits.items():
        checks[name] = {"value": got.get(name, float("inf")),
                        "limit": float(limit)}
    correct = bool(served) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    extra = {k: v for k, v in got.items() if k not in checks}
    return checks, correct, extra
