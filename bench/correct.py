"""What decides ``correct``: served tokens against the float32 reference.

Once the window has closed, every request that the run finished goes
through the reference that the configuration names (``reference``: a module
of ``bench/reference/``, whose contract ``bench/harness.py`` states): it
runs once over each prompt with its served tokens, and for every served
token reads how far that token's reference logit lies below the
reference's best at its position (0 where the served token is the
reference's own pick). The widest such gap over all of them is held to the
cell's limit (``bench/cells/<workload>.json``: ``max_logit_gap``); the mean
gap and the share of tokens that are not the reference's pick are readings.

The control (``control=True``, used by ``bench/limits.py`` and the tests,
never by a benchmark run) reads, at the same positions, the gap of the token
that the control (the reference one precision lower: int8 for the bf16
configurations) would put first; :func:`as_control` puts those readings in
the program's place, so that the control is judged by the same
:func:`correct`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

import numpy as np


@dataclasses.dataclass
class Served:
    """A finished request: its prompt and the tokens the engine served."""

    prompt: np.ndarray
    tokens: np.ndarray
    max_new: int


def compare(reference, params, config: Dict, reqs: Sequence[Served], bucket: int, control: bool = False) -> Dict:
    """How far the served tokens' logits in ``reference`` (a module of
    ``bench/reference/``, run at ``config``) lie below its best: the widest
    gap, the mean gap, and the share of served tokens that are not the
    reference's own pick; with ``control``, the same of the control's picks
    at the same positions."""
    ref_cfg = reference.RefConfig.from_config(config)
    served, low = [], []
    short = 0
    for r in reqs:
        plen, n = len(r.prompt), len(r.tokens)
        short += int(n < r.max_new)
        seq = np.concatenate([r.prompt, r.tokens[:-1]]).astype(np.int32)
        if len(seq) > bucket:
            raise ValueError(f"a served sequence of {len(seq)} tokens exceeds the reference's {bucket}")
        toks = np.zeros(bucket, np.int32)
        toks[: len(seq)] = seq
        nxt = np.zeros(bucket, np.int32)
        nxt[: len(seq) - 1] = seq[1:]
        nxt[len(seq) - 1] = r.tokens[-1]
        gap, ctrl = reference.gaps(params, toks, nxt, ref_cfg, control)
        at = slice(plen - 1, plen - 1 + n)
        served.append(np.asarray(gap)[at])
        low.append(np.asarray(ctrl)[at])
    out = {"compared": int(sum(len(g) for g in served)), "requests": len(reqs), "short": short}
    for name, gaps in (("", served), ("control_", low)):
        g = np.concatenate(gaps) if gaps else np.zeros(0)
        out[f"{name}widest_gap"] = float(g.max()) if g.size else 0.0
        out[f"{name}mean_gap"] = float(g.mean()) if g.size else 0.0
        out[f"{name}missed_share"] = float(np.mean(g > 0)) if g.size else 0.0
    return out


def as_control(cmp: Dict) -> Dict:
    """The comparison with the control's readings in the program's place."""
    return {**cmp, **{k: cmp[f"control_{k}"] for k in ("widest_gap", "mean_gap", "missed_share")}}


def checks(cmp: Dict, limits: Dict) -> Dict:
    """Each number compared, beside its limit: the widest logit gap, and
    compared requests that ended short of their length."""
    return {
        "max_logit_gap": {"value": cmp["widest_gap"], "limit": float(limits["max_logit_gap"])},
        "short_requests": {"value": cmp["short"], "limit": 0},
    }


def correct(cmp: Dict, limits: Dict) -> bool:
    """Requests were compared, and every number is within its limit."""
    return cmp["requests"] > 0 and all(c["value"] <= c["limit"] for c in checks(cmp, limits).values())
