"""The benchmark's copies of the data generators write what the program's
own generators write, so the yardstick starts where the program's
benchmarks stood.  Run with `pytest bench/`."""
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(BENCH, "generators"),
                os.path.join(os.path.dirname(BENCH), "src")]

import cnn  # noqa: E402
import mandel_trace  # noqa: E402
import reference  # noqa: E402
from tracer import Tracer  # noqa: E402


def _config(name):
    import json
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mandel_trace_parses_to_the_programs_paper_graph(tmp_path):
    from repro.core import build_graph
    from repro.trace import load_graph
    config = _config("paper-mandel")
    path = str(tmp_path / "mandel.ndjson")
    mandel_trace.write(config, path)
    ours = load_graph(path)
    theirs = build_graph("mandel", "paper", cache_dir=None)
    assert (ours.n, ours.num_edges) == (308_080, 512_361)
    assert ours.n == theirs.n
    for key in ("src", "dst"):
        assert np.array_equal(getattr(ours, key), getattr(theirs, key)), key
    assert np.all(ours.w == 8.0)


def test_cnn_is_the_programs_paper_graph(tmp_path):
    from repro.core import build_graph
    config = _config("paper-cnn")
    t = Tracer("cnn/paper")
    cnn._cnn(t, seed=config["data_seed"], **config["sizes"])
    ours = t.graph()
    theirs = build_graph("cnn", "paper", cache_dir=None)
    assert (ours["n"], len(ours["src"])) == (760_083, 1_242_234)
    assert ours["n"] == theirs.n
    for key in ("src", "dst", "w"):
        assert np.array_equal(ours[key], getattr(theirs, key)), key


def test_cnn_graph_does_not_depend_on_the_seed():
    a, b = Tracer("a"), Tracer("b")
    cnn._cnn(a, img_side=8, seed=0)
    cnn._cnn(b, img_side=8, seed=2**31 + 7)
    ga, gb = a.graph(), b.graph()
    assert ga["n"] == gb["n"]
    for key in ("src", "dst", "w"):
        assert np.array_equal(ga[key], gb[key])


def test_reference_parse_matches_the_programs(tmp_path):
    from repro.trace import load_graph
    path = str(tmp_path / "t.ndjson")
    mandel_trace.write({"sizes": {"npoints": 100, "max_iter": 24}}, path)
    ours = reference.load(path)
    theirs = load_graph(path)
    assert ours["n"] == theirs.n
    for key in ("src", "dst", "w"):
        assert np.array_equal(ours[key], getattr(theirs, key)), key
