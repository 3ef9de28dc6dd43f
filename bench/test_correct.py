"""The comparison that decides `correct` fails what it must.

* The control, the reference computed with bfloat16 values summed in
  float32 in place of the served plan, breaks both simulated-time limits
  at every lambda of the grid, on a small CNN and a small Mandel trace.
* A whole run of the harness on the CPU (Pallas in interpret mode, both
  configurations at a small size), with the timed path broken
  underneath, reports `correct` false for each fault a plan request can
  have: a service that answers with the plan of the request before (its
  state unchanged), half of the graph's edges left out, and a device
  reduction altered where it is produced.  The same run unbroken is
  correct.  (One chip: there is no exchange between chips to leave out.)

Run with `pytest bench/`.
"""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(BENCH, "generators"),
                os.path.join(os.path.dirname(BENCH), "src")]

import reference  # noqa: E402
import run  # noqa: E402

CONFIG = {"name": "small-cnn", "generator": "cnn",
          "sizes": {"img_side": 10, "c1": 6, "c2": 12}, "data_seed": 0}
MANDEL = {"name": "small-mandel", "generator": "mandel_trace",
          "sizes": {"npoints": 400, "max_iter": 24}}
TRAFFIC = {"p": 8, "method": "wb_libra",
           "lams": [1.0, 1.125, 1.25, 1.375, 1.5], "check_requests": 2}
SEED = 2**31 + 11


def _breaks_a_limit(numbers: dict) -> bool:
    return any(v > reference.LIMITS[k] for k, v in numbers.items())


@pytest.mark.parametrize("config", [CONFIG, MANDEL], ids=lambda c: c["name"])
@pytest.mark.parametrize("lam", TRAFFIC["lams"])
def test_control_breaks_the_time_limits(tmp_path, config, lam):
    graph = reference.load(run.write_source(config, str(tmp_path)))
    want = reference.plan(graph, TRAFFIC["p"], lam)
    control = reference.compare(
        reference.plan(graph, TRAFFIC["p"], lam, "bfloat16"), want)
    for name in ("exec_time_rel_err", "core_times_rel_err"):
        assert control[name] > reference.LIMITS[name], name
    assert not _breaks_a_limit(reference.compare(want, want))


# the small Mandel trace plans alike at p=8 for every lambda above 1.0,
# so a stale plan could pass as right; at p=64 each lambda plans apart
@pytest.fixture(scope="module", params=[(CONFIG, TRAFFIC),
                                        (MANDEL, dict(TRAFFIC, p=64))],
                ids=lambda c: c[0]["name"])
def harness(request, tmp_path_factory):
    import jax
    root = str(tmp_path_factory.mktemp("checkout"))
    dev = jax.devices()[0]
    config, traffic = request.param

    def once():
        _, result = run.run_cell({"name": "small", "chips": 1}, config,
                                 traffic, [], SEED, 3.0, False, dev,
                                 root=root)
        return result
    return once


def test_unbroken_run_is_correct(harness):
    result = harness()
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["checks"]) == set(reference.LIMITS)


def test_stale_plan_is_caught(harness, monkeypatch):
    from repro.serve import PlanService
    plan = PlanService.plan
    last = {}

    def stale(self, req):       # answers each request with the last plan
        fresh = plan(self, req)
        stale_resp, last["resp"] = last.get("resp", fresh), fresh
        return stale_resp
    monkeypatch.setattr(PlanService, "plan", stale)
    result = harness()
    assert result["correct"] is False
    assert result["checks"]["assignment_mismatch"]["value"] > 0


def test_half_the_edges_is_caught(harness, monkeypatch):
    import repro.trace
    from repro.core.graph import IRGraph
    load_graph = repro.trace.load_graph

    def half(source, **kw):
        g = load_graph(source, **kw)
        k = g.num_edges // 2
        return IRGraph(n=g.n, src=g.src[:k], dst=g.dst[:k], w=g.w[:k],
                       name=g.name)
    monkeypatch.setattr(repro.trace, "load_graph", half)
    result = harness()
    assert result["correct"] is False
    assert result["checks"]["graph_mismatch"]["value"] > 0


def test_altered_reduction_is_caught(harness, monkeypatch):
    from repro.core.pallas import segsum
    reduce = segsum._reduce

    def altered(*args, **kw):
        out = reduce(*args, **kw)
        return out.at[0].add(np.asarray(1, out.dtype))
    monkeypatch.setattr(segsum, "_reduce", altered)
    result = harness()
    assert result["correct"] is False
