"""The DeepSeek-V3 training-step configuration at a tiny size.

* The generator's copy of the jaxpr -> graph construction equals the
  program's `repro.core.jaxpr_graph.jaxpr_to_graph` (scans uncapped) bit
  for bit on a tiny DeepSeek-V3-shaped step.
* That step, served by `PlanService(backend="pallas")` (interpret mode on
  the CPU), equals `reference.py` on every number `correct` compares,
  with its byte weights multiplied by 2^24 so that the loads pass 2^31
  (the tiny widths alone stay far below it, as the published ones do
  not), and 1 added to each: multiples of 2^24 whose sums stay below
  2^48 are exact in float32, and an exact float32 sum by luck would hide
  the fault of the next test.  With the wide path taken away (such data
  summed in float32, as before it existed) the loads no longer match.
* The three metrics of the wide path on a synthetic profiler trace with
  wide-kernel ops (testdata/wide.xplane.txt).

Run with `pytest bench/`.
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(BENCH, "generators"),
                os.path.join(os.path.dirname(BENCH), "src")]

import deepseek_v3_step  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

# DeepSeek-V3's structure at tiny widths: 1 dense and 4 MoE layers of 8
# routed experts (2 per token, 4 groups, top-2 groups) and the MTP module
TINY = {"name": "tiny-dsv3", "generator": "deepseek_v3_step",
        "hidden_size": 64, "num_hidden_layers": 5,
        "first_k_dense_replace": 1, "intermediate_size": 96,
        "moe_intermediate_size": 16, "n_routed_experts": 8,
        "n_shared_experts": 1, "num_experts_per_tok": 2, "n_group": 4,
        "topk_group": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4, "v_head_dim": 8, "num_attention_heads": 2,
        "vocab_size": 50, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "num_nextn_predict_layers": 1, "tie_word_embeddings": False,
        "step": {"batch": 1, "seq_len": 16, "expert_capacity": 4,
                 "mtp_loss_weight": 0.3, "param_dtype": "bfloat16"}}
SCALE = 2.0 ** 24
EXACT = ("graph_mismatch", "assignment_mismatch", "loads_mismatch",
         "edge_counts_mismatch", "replica_csr_mismatch", "core_of_mismatch",
         "comm_bytes_err")


@pytest.fixture(scope="module")
def tiny_jaxpr():
    return deepseek_v3_step.step_jaxpr(TINY)


def test_graph_copy_is_the_programs_construction(tiny_jaxpr):
    from repro.core.jaxpr_graph import jaxpr_to_graph
    ours = deepseek_v3_step.jaxpr_to_graph(tiny_jaxpr, TINY["name"])
    theirs = jaxpr_to_graph(tiny_jaxpr, max_scan_unroll=10 ** 9)
    assert ours["n"] == theirs.n
    for key in ("src", "dst", "w"):
        assert np.array_equal(ours[key], getattr(theirs, key)), key
    # every layer is in the graph: capped at 2 copies a scan it shrinks
    capped = jaxpr_to_graph(tiny_jaxpr, max_scan_unroll=2)
    assert capped.n < ours["n"]


def test_configuration_records_the_generated_graph():
    """The counts in the configuration are what the generator writes at
    published widths and full depth (about 20 s here)."""
    with open(os.path.join(BENCH, "configs",
                           "deepseek-v3-train-step.json")) as f:
        config = json.load(f)
    assert config["step"]["expert_capacity"] == (
        config["step"]["seq_len"] * config["num_experts_per_tok"]
        // config["n_routed_experts"])
    g = deepseek_v3_step.jaxpr_to_graph(
        deepseek_v3_step.step_jaxpr(config), config["name"])
    assert (g["n"], len(g["src"]), int(g["w"].sum())) == (
        config["graph"]["vertices"], config["graph"]["edges"],
        config["graph"]["total_weight_bytes"])
    assert 2 ** 31 < g["w"].sum() < 2 ** 53


@pytest.fixture(scope="module")
def tiny_source(tiny_jaxpr, tmp_path_factory):
    g = deepseek_v3_step.jaxpr_to_graph(tiny_jaxpr, TINY["name"])
    g["w"] = g["w"] * SCALE + 1.0
    assert 2 ** 31 <= g["w"].sum() < 2 ** 53
    path = tmp_path_factory.mktemp("dsv3") / "tiny-dsv3.npz"
    with open(path, "wb") as f:
        np.savez_compressed(f, **g)
    return str(path)


TRAFFIC = {"p": 64, "method": "wb_libra",
           "lams": [1.0, 1.125, 1.25, 1.375, 1.5], "check_requests": 5}


def _serve_and_check(source, tmp_path):
    from repro import obs
    loop = run.Loop(source, TRAFFIC, TRAFFIC["lams"], str(tmp_path / "plans"))
    col = obs.enable()
    try:
        done = loop.serve(None, annotate=False)
    finally:
        obs.disable()
    assert all(d[1] is not None for d in done)
    widen = [e for e in col.events if e["name"] == "segsum.widen"]
    return run.check(done, source, TRAFFIC, 2 ** 31 + 11), widen


def test_tiny_step_served_exactly_past_2_31(tiny_source, tmp_path):
    numbers, widen = _serve_and_check(tiny_source, tmp_path)
    assert set(numbers) == set(reference.LIMITS)
    for name in EXACT:
        assert numbers[name] == 0, name
    for name, value in numbers.items():
        assert value <= reference.LIMITS[name], name
    # one wide reduction a plan: the loads, edges in and clusters out
    m = len(np.load(tiny_source)["src"])
    assert [(e["args"]["elements"], e["args"]["slots"]) for e in widen] \
        == [(m, TRAFFIC["p"])] * len(TRAFFIC["lams"])


def test_without_the_wide_path_the_loads_are_wrong(tiny_source, tmp_path,
                                                   monkeypatch):
    from repro.core.pallas import segsum
    narrow = segsum.narrow

    def float32_past_2_31(values, magnitude=None):
        out = narrow(values, magnitude)
        return out.astype(np.float32) if out.dtype == np.int64 else out
    monkeypatch.setattr(segsum, "narrow", float32_past_2_31)
    numbers, widen = _serve_and_check(tiny_source, tmp_path)
    assert not widen
    assert numbers["loads_mismatch"] > 0


# ---------------------------------------------------------------------- #
# the wide path's metrics on a synthetic trace
# ---------------------------------------------------------------------- #
def _metric(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REQUESTS = [(10.0, 10.00003), (10.00003, 10.00005)]
SPANS = [
    {"name": "serve.plan_cold", "ph": "X", "ts": 10e6, "dur": 30.0},
    {"name": "segsum.widen", "ph": "X", "ts": 10e6 + 9, "dur": 1.5,
     "args": {"elements": 1000, "slots": 64}},
    {"name": "segsum.widen", "ph": "X", "ts": 10e6 + 29, "dur": 0.5,
     "args": {"elements": 1000, "slots": 64}},
]


@pytest.fixture(scope="module")
def wide_profile(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(os.path.join(BENCH, "testdata", "wide.xplane.txt")) as f:
        text = f.read()
    path = tmp_path_factory.mktemp("xplane") / "wide.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.reduce(trace_reduce.load(str(path)), REQUESTS,
                               SPANS)


def test_wide_metrics(wide_profile):
    ctx = {"plans": 2, "profile": wide_profile, "spans": SPANS, "sizes": [],
           "device_kind": "TPU v5 lite"}
    # [10000, 16000) and [30000, 32000) of the wide kernel over 2 plans
    assert _metric("wide_segsum_kernel_ms").read(ctx) == pytest.approx(4e-3)
    # the plain kernel's metric keeps its meaning: no wide op in it
    assert _metric("segsum_kernel_ms").read(ctx) == pytest.approx(4.5e-3)
    assert _metric("widen_ms").read(ctx) == pytest.approx(1e-3)
    roof = _metric("wide_reductions_roofline")
    assert roof.reduction_bytes(1000, 64) == 4 * (3 * 1000 + 2 * 64)
    assert roof.read(ctx) == pytest.approx(
        100 * 2 * 4 * (3000 + 128) / 819e9 / 8e-6)
    with pytest.raises(KeyError):
        roof.read({**ctx, "device_kind": "cpu"})


def test_wide_metrics_read_nothing_without_the_wide_path(wide_profile):
    ops = {n: s for n, s in wide_profile["ops"].items()
           if "wide_segsum" not in n}
    ctx = {"plans": 2, "profile": {**wide_profile, "ops": ops},
           "spans": SPANS[:1], "sizes": [], "device_kind": "TPU v5 lite"}
    for name in ("widen_ms", "wide_segsum_kernel_ms",
                 "wide_reductions_roofline"):
        assert _metric(name).read(ctx) is None, name
