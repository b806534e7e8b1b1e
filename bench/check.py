"""What decides ``correct``: the served tokens against the plain reference.

After the window closes, a sample of the requests the window finished is
drawn from the seed: the longest finished request, one base and one
variant request where there are such, then others at random until the
sample holds ``MIN_TOKENS`` served tokens or ``MAX_REQUESTS`` requests.
The reference runs once over each prompt with its served tokens
(teacher-forced, float32 at the highest matmul precision), and for every
served token reads how far its logit lies below the reference's best
logit at that position.  The widest such gap is the number compared.

The control (``bench/calibrate.py``, never the benchmark's own runs) is the
reference in a lower precision put in the program's place: at each
position of the same prompts and tokens it reads the gap, under the
float32 reference, of the token the lower precision puts first.
"""
from __future__ import annotations

import numpy as np

MIN_TOKENS = 300
MAX_REQUESTS = 8


def finished(sent: list) -> list:
    return [s for s in sent if s.handle.status == "done"
            and len(s.handle.out_tokens) == s.req.max_new_tokens]


def sample(sent: list, seed: int) -> list:
    done = finished(sent)
    if not done:
        return []
    rng = np.random.default_rng([seed, 7])
    order = [done[i] for i in rng.permutation(len(done))]
    pick = [max(done, key=lambda s: (s.req.max_new_tokens, -s.rid))]
    for want_base in (True, False):
        for s in order:
            if (s.req.variant == "__base__") == want_base:
                if s not in pick:
                    pick.append(s)
                break
    for s in order:
        if (sum(p.req.max_new_tokens for p in pick) >= MIN_TOKENS
                or len(pick) >= MAX_REQUESTS):
            break
        if s not in pick:
            pick.append(s)
    return pick


def sequences(picked: list) -> list:
    """(variant, tokens, positions, served) per sampled request: the
    prompt followed by every served token but the last; position p's
    logits predict served[p - positions[0]]."""
    out = []
    for s in picked:
        served = np.asarray(list(s.handle.out_tokens), np.int64)
        prompt = np.asarray(s.req.prompt, np.int64)
        tokens = np.concatenate([prompt, served[:-1]])
        positions = np.arange(len(prompt) - 1, len(tokens))
        out.append((s.req.variant, tokens, positions, served))
    return out


def gaps(ref, conf: dict, weights: dict, variants: dict, seqs: list,
         control: str | None = None) -> dict:
    """Per sampled request, the gap of each served token under the float32
    reference; with ``control``, also the gap of the token the control
    precision puts first at each position."""
    program, ctrl = [], []
    for variant, tokens, positions, served in seqs:
        v = None if variant == "__base__" else variants[variant]
        lg = ref.logits(conf, weights, v, tokens, positions)
        best = lg.max(-1)
        program.append(best - lg[np.arange(len(served)), served])
        if control is not None:
            lc = ref.logits(conf, weights, v, tokens, positions,
                            precision=control)
            ctrl.append(best - lg[np.arange(len(served)), lc.argmax(-1)])
    out = {"program": program}
    if control is not None:
        out["control"] = ctrl
    return out


def widest(per_request: list) -> float:
    return float(max(float(np.max(g)) for g in per_request))


def verdict(checks: dict) -> bool:
    """``correct``: every number compared has a reading within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
