"""The one generator: seeds change the order of the work, not its amount."""
import numpy as np
import pytest

from bench import traffic as T
from bench.spec import BENCH, _json

MIXES = ["mixed.open", "base.open", "mixed.backlog"]
VARIANTS = ["v0", "v1", "v2", "v3"]


def _mix(name):
    """The cell's mix, and the generator's other modes built from it: every
    request for the base, and closed (backlog) arrivals."""
    mix = _json("traffic", "mixed.open")
    if name == "base.open":
        mix["base_share"] = 1.0
    elif name == "mixed.backlog":
        mix = dict(mix, arrivals="backlog", queue_per_lane=2)
        del mix["rate_rps"], mix["knee_rps"]
    return mix


def _gen(mix, seed, seconds=51.0):
    return T.generate(_mix(mix), seed, variants=VARIANTS, vocab=102400,
                      seconds=seconds)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a, b = _gen(mix, 2 ** 33 + 5), _gen(mix, 2 ** 33 + 5)
    assert [(r.due, r.variant, r.max_new_tokens) for r in a] == \
        [(r.due, r.variant, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_other_seed_other_requests(mix):
    a, b = _gen(mix, 5), _gen(mix, 2 ** 33 + 5)
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert [r.max_new_tokens for r in a] != [r.max_new_tokens for r in b]


def test_every_seed_gets_the_same_tape():
    """Gaps and lengths of the whole tape are one set in another order."""
    mix = _mix("mixed.backlog")
    ref = sorted(r.max_new_tokens for r in _gen("mixed.backlog", 1))
    for seed in (2, 3, 2 ** 40 + 1):
        assert sorted(r.max_new_tokens for r in
                      _gen("mixed.backlog", seed)) == ref
    assert len(ref) == mix["tape"]
    assert min(ref) >= mix["output"]["min"]
    assert max(ref) <= mix["output"]["max"]
    assert abs(float(np.median(ref)) - mix["output"]["median"]) <= 1


def test_open_arrivals_follow_the_rate():
    mix = _mix("mixed.open")
    reqs = _gen("mixed.open", 9, seconds=400.0)
    dues = np.array([r.due for r in reqs])
    assert dues[0] == 0.0 and np.all(np.diff(dues) > 0)
    assert dues[-1] < 400.0
    assert abs(len(reqs) / 400.0 - mix["rate_rps"]) < 0.15 * mix["rate_rps"]


def test_variant_shares():
    reqs = _gen("mixed.backlog", 11)
    n = len(reqs)
    counts = {v: sum(r.variant == v for r in reqs)
              for v in ["__base__"] + VARIANTS}
    assert abs(counts["__base__"] / n - 0.2) < 0.01
    # Zipf(1) over four variants: ranks in order
    assert counts["v0"] > counts["v1"] > counts["v2"] > counts["v3"]
    base_only = _gen("base.open", 11)
    assert {r.variant for r in base_only} == {"__base__"}


def test_prompts_are_exactly_prompt_len():
    for mix in MIXES:
        plen = _mix(mix)["prompt_len"]
        assert {len(r.prompt) for r in _gen(mix, 4)} == {plen}


def test_mixes_are_data_files():
    """A mix is parameters for the one generator, never code."""
    files = [p for p in (BENCH / "traffic").iterdir() if p.is_file()]
    assert files and all(p.suffix == ".json" for p in files)


@pytest.mark.parametrize("slopes,ends,want", [
    ([0.0, 0.05, 0.4, 0.9], [0, 2, 9, 20], 0.8),   # queue grows from 1.2
    ([0.0, 0.05, 0.02, 0.9], [0, 8, 1, 20], 0.4),  # 0.8 ends too deep
    ([0.3, 0.0, 0.0, 0.0], [9, 0, 0, 0], None),    # the lowest rate fails
])
def test_sweep_knee_is_the_last_rate_whose_queue_held(slopes, ends, want):
    from bench import sweep
    rates = [1.2, 0.4, 0.8, 1.6]
    rows = [{"rate_rps": r, "waiting_slope_per_s": s, "waiting_end": e}
            for r, s, e in zip(sorted(rates), slopes, ends)]
    assert sweep.knee(list(reversed(rows)), lanes=6) == want
