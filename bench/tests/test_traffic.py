from collections import Counter
from itertools import islice

import numpy as np
import pytest

from bench.harness.spec import resolve


def _draw(cell, seed, phase, n=12, root=None):
    spec = resolve(cell, root) if root else resolve(cell)
    kind = spec["kind"]
    return [list(islice(s, n)) for s in kind.streams(spec["mix"], seed, phase, spec["params"].sample)]


def test_streams_depend_only_on_the_seed():
    kind = resolve("isolated_c8")["kind"]
    big = 2**31 + 12345
    assert _draw("isolated_c8", big, kind.WINDOW) == _draw("isolated_c8", big, kind.WINDOW)
    assert _draw("isolated_c8", big, kind.WINDOW) != _draw("isolated_c8", big + 1, kind.WINDOW)
    assert _draw("isolated_c8", big, kind.WINDOW) != _draw("isolated_c8", big, kind.WARMUP)


def test_graft_and_isolated_draw_identical_streams(tiny_root):
    kind = resolve("isolated_c8")["kind"]
    for seed in (0, 7, 3_000_000_000):
        assert (_draw("graft_c8", seed, kind.WINDOW, root=tiny_root)
                == _draw("isolated_c8", seed, kind.WINDOW, root=tiny_root))


def test_the_permutation_table_holds_permutations():
    spec = resolve("isolated_c8")
    rows = [row for row in spec["kind"].stream_orders(dict(spec["mix"], templates=[
        f"q{n}" for n in range(1, 23)]))]
    assert len(rows) >= spec["mix"]["first_stream"] + spec["mix"]["clients"]
    for row in rows:
        assert sorted(int(t[1:]) for t in row) == list(range(1, 23))
    # stream 1 of TPC-H Appendix A, kept to Q1 and Q3-Q10
    assert spec["kind"].stream_orders(spec["mix"])[1] == [
        "q3", "q5", "q7", "q6", "q10", "q8", "q9", "q1", "q4"]


@pytest.mark.parametrize("seed", [1, 99, 2**33])
def test_every_seed_runs_the_same_templates_in_the_same_order(seed):
    spec = resolve("isolated_c8")
    kind, mix = spec["kind"], spec["mix"]
    n = len(mix["templates"])
    base = [[t for t, _ in c] for c in _draw("isolated_c8", 5, kind.WINDOW, n=2 * n)]
    got = [[t for t, _ in c] for c in _draw("isolated_c8", seed, kind.WINDOW, n=2 * n)]
    assert got == base
    for c, seq in enumerate(got):  # each pass runs every template once
        assert Counter(seq[:n]) == Counter(mix["templates"]), c
        assert seq[:n] == seq[n:]
    # the clients run streams 1..8, not one order shifted
    assert len({tuple(seq[:n]) for seq in got}) == mix["clients"]


def test_the_warm_up_runs_every_template_with_fixed_work():
    spec = resolve("isolated_c8")
    kind, mix = spec["kind"], spec["mix"]
    per = mix["warmup_per_client"]
    for seed in (3, 2**32 + 1):
        warm = _draw("isolated_c8", seed, kind.WARMUP, n=per + 5)
        assert [len(c) for c in warm] == [per] * mix["clients"]
        assert {t for c in warm for t, _ in c} == set(mix["templates"])
    assert ([[t for t, _ in c] for c in _draw("isolated_c8", 3, kind.WARMUP)]
            == [[t for t, _ in c] for c in _draw("isolated_c8", 4, kind.WARMUP)])


def test_parameters_stay_in_their_domains():
    spec = resolve("isolated_c8")
    sample = spec["params"].sample
    assert spec["params"].COLORS == spec["data"].COLORS and len(spec["data"].COLORS) == 92
    rng = np.random.default_rng(5)
    for _ in range(200):
        p = sample("q6", rng)
        assert p["quantity"] in (24.0, 25.0) and 0.02 <= p["discount"] <= 0.09
        p = sample("q7", rng)
        assert p["nation1"] != p["nation2"]
        p = sample("q1", rng)
        assert 60 <= p["delta"] <= 120
        p = sample("q10", rng)
        assert spec["data"].days("1993-02-01") <= p["date"] <= spec["data"].days("1995-01-01")
        assert sample("q9", rng)["color"] in spec["data"].COLORS
