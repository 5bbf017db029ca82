"""``tpch_plans_graft`` (Q4's EXISTS as a semi-join) against the plain
reference, on the CPU at ``TINY_SF``."""

import json

import numpy as np
import pytest

from bench.harness import cell, check
from bench.harness.spec import load_module

SEED = 2**31 + 91


def _config(root, **engine):
    config = json.loads((root / "bench" / "configs" / "tpch_sf0.3_graft.json").read_text())
    config["engine"] = dict(config["engine"], **engine)
    return config


@pytest.mark.parametrize("backend", ["pallas", "reference"])
def test_every_template_equals_the_reference(tiny_root, backend):
    configs, traffic = tiny_root / "bench" / "configs", tiny_root / "bench" / "traffic"
    config = _config(tiny_root, backend=backend)
    plans = load_module(configs, config["plans"])
    ref = load_module(configs, config["reference"])
    params = load_module(traffic, "tpch_params")
    mix = json.loads((traffic / "tpch_streams_c8.json").read_text())
    tables = load_module(configs, config["data"]).generate(config["scale_factor"], SEED)
    db, session = cell.open_session(config, tables)
    rng = np.random.default_rng(SEED)
    sent = []
    for t in mix["templates"] * 2:
        p = params.sample(t, rng)
        q = plans.make_query(db, t, p, arrival=session.now)
        sent.append((t, p, q, session.submit(q)))
    session.run()
    assert len({q.qid for _, _, q, _ in sent}) == len(sent)
    q4 = [q for t, _, q, _ in sent if t == "q4"]
    assert all(q.plan.input.input.kind == "semi" for q in q4)
    answers = [{"template": t, "params": p, "result": f.result()} for t, p, _, f in sent]
    read = check.readings(answers, lambda t, p: ref.answer(tables, t, p), 0)
    assert read["compared"] == 18 and read["wrong_shape"] == 0
    assert read["max_rel_err"] <= config["limits"]["max_rel_err"]
    assert session.counters["semi_builds"] + session.counters["semi_attaches"] == 2
