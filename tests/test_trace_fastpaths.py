"""The two trace fast paths: the native NDJSON scanner (`trace.scan`)
and the `.rtb` binary columnar container (`trace.binfmt`).

Both are *transparent accelerators*: every test here is a differential
against the sequential streaming interpreter, which remains the semantic
reference.  The scanner must be bit-identical where it engages and fall
back (whole-file) everywhere else; `.rtb` containers must round-trip the
exact arrays `convert` serialized and be accepted anywhere an NDJSON
path is.
"""
import gzip
import json
import os
import struct

import numpy as np
import pytest

from repro import obs
from repro.core import run_pipeline
from repro.core.graph import IRGraph
from repro.trace import (BINARY_MAGIC, BINARY_VERSION, BinaryFormatError,
                         SCANNER_ENV, TraceFormatError, ingest_trace_with_stats,
                         is_binary_trace_path, iter_synthetic_trace,
                         iter_trace_bin_chunks, load_graph, read_trace_bin,
                         read_trace_bin_header, scanner_enabled,
                         try_scan_ingest, write_trace_bin)
from repro.trace import scan


def _write_synth(tmp_path, lines=1500, seed=11, name="t.ndjson"):
    p = tmp_path / name
    p.write_text("\n".join(iter_synthetic_trace(lines, seed=seed)) + "\n")
    return str(p)


def _seq(monkeypatch, source, **kw):
    """Sequential-reference ingest: scanner forced off via the env knob."""
    monkeypatch.setenv(SCANNER_ENV, "0")
    try:
        return ingest_trace_with_stats(source, **kw)
    finally:
        monkeypatch.delenv(SCANNER_ENV)


def _assert_graphs_identical(a: IRGraph, b: IRGraph):
    assert a.n == b.n
    assert np.array_equal(a.src, b.src)
    assert np.array_equal(a.dst, b.dst)
    assert np.array_equal(a.w, b.w)          # exact: bit-identity, no tol
    assert a.node_labels == b.node_labels


def _traced(source, **kw):
    """Ingest under a fresh obs collector: (graph, stats, the tokenizer arg
    of the scan span or None, the trace.scan_fallback reasons)."""
    with obs.scoped(merge=False) as col:
        g, st = ingest_trace_with_stats(source, **kw)
    spans = [e for e in col.events
             if e["name"] == "trace.ingest" and e["ph"] == "X"]
    reasons = [e["args"]["reason"] for e in col.events
               if e["name"] == "trace.scan_fallback"]
    tok = (spans[0]["args"].get("tokenizer")
           if spans and spans[0]["args"]["engine"] == "scan" else None)
    return g, st, tok, reasons


@pytest.fixture
def tokenizer():
    """The scanner's tokenizer arg; skips where no C compiler builds the
    tokenizer (every file then takes the interpreter)."""
    if scan._native_tokenizer() is None:
        pytest.skip("native tokenizer unavailable (no C compiler, or "
                    "REPRO_NO_NATIVE is set)")
    return "native"


# ---------------------------------------------------------------------- #
# scanner: bit-identity where it engages
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("model", ["bytes", "memop-latency"])
def test_scanner_matches_sequential_synth(tmp_path, monkeypatch, model,
                                          tokenizer):
    path = _write_synth(tmp_path, 2500, seed=3)
    g_ref, st_ref = _seq(monkeypatch, path, weight_model=model,
                         keep_labels=True)
    g, st, tok, _ = _traced(path, weight_model=model, keep_labels=True)
    assert st_ref.engine == "stream" and st.engine == "scan"
    assert tok == tokenizer
    _assert_graphs_identical(g, g_ref)
    # every semantic stat matches; engine/peak are engine provenance
    sa, sb = st.summary(), st_ref.summary()
    for k in ("engine", "peak_chunk_edges"):
        sa.pop(k), sb.pop(k)
    assert sa == sb


def test_scanner_matches_on_committed_fixtures(monkeypatch, tokenizer):
    import pathlib
    tdir = pathlib.Path(__file__).resolve().parent.parent / "examples/traces"
    for fixture in ("toy_loop.ndjson", "mlp_jaxpr.ndjson"):
        path = str(tdir / fixture)
        g_ref, _ = _seq(monkeypatch, path, keep_labels=True)
        g, st, tok, _ = _traced(path, keep_labels=True)
        assert st.engine == "scan" and tok == tokenizer, fixture
        _assert_graphs_identical(g, g_ref)


def test_scanner_gzip_source(tmp_path, monkeypatch, tokenizer):
    text = "\n".join(iter_synthetic_trace(900, seed=5)) + "\n"
    gz = tmp_path / "t.ndjson.gz"
    with gzip.open(gz, "wt", encoding="utf-8") as f:
        f.write(text)
    g_ref, _ = _seq(monkeypatch, str(gz))
    g, st, tok, _ = _traced(str(gz))
    assert st.engine == "scan" and tok == tokenizer
    _assert_graphs_identical(g, g_ref)


def test_scanner_symbol_order_matches_sequential(tmp_path, monkeypatch,
                                                 tokenizer):
    """Live-ins and `const:` uses materialise in trace order, whatever
    the order of their names: symbols first appear here out of
    lexicographic order (and out of little-endian word order), in two
    functions that reuse the same ids, with a block re-entry, a void def,
    a repeated use and a redefinition."""
    recs = [
        ("f", "b0", 0, "load", "v9", ["zeta", "const:7", "alpha"]),
        ("f", "b0", 1, "add", "v2", ["v9", "beta", "const:1", "zeta"]),
        ("f", "b0", 0, "mul", "v1", ["ab", "ba", "v2", "v2"]),
        ("g", "e", 3, "sub", "v9", ["alpha", "zeta", "const:7"]),
        ("g", "e", 4, "store", None, ["v9", "ba", "ab"]),
        ("f", "b1", 0, "add", "alpha", ["alpha", "v1", "const:0"]),
        ("f", "b1", 1, "neg", "v3", ["alpha", "yy", "aa"]),
    ]
    lines = []
    for fn, bb, i, op, d, uses in recs:
        tys = ",".join('"i%d"' % (8 * (k + 1)) for k in range(len(uses)))
        dj = "null" if d is None else f'"{d}"'
        lines.append(f'{{"fn":"{fn}","bb":"{bb}","pp":"{fn}:{bb}:i{i}",'
                     f'"op":"{op}","def":{dj},"uses":'
                     + json.dumps(uses, separators=(",", ":"))
                     + f',"def_ty":"i64","use_tys":[{tys}]}}')
    path = tmp_path / "order.ndjson"
    path.write_text("\n".join(lines) + "\n")
    g_ref, st_ref = _seq(monkeypatch, str(path), keep_labels=True)
    g, st, tok, _ = _traced(str(path), keep_labels=True)
    assert st.engine == "scan" and tok == tokenizer
    _assert_graphs_identical(g, g_ref)
    assert (st.livein_uses, st.const_uses) == (st_ref.livein_uses,
                                               st_ref.const_uses)
    assert g.node_labels[:4] == ["load", "zeta", "const", "alpha"]


def test_scanner_large_trace(tmp_path, monkeypatch, tokenizer):
    """The default dispatch scans a trace past 24 MiB, bit-identical to
    the interpreter."""
    from repro.trace import synthesize_trace
    monkeypatch.delenv(SCANNER_ENV, raising=False)
    path = str(tmp_path / "big.ndjson")
    synthesize_trace(path, 190_000, seed=8)
    assert os.path.getsize(path) > 24 * (1 << 20)
    g, st, tok, reasons = _traced(path)
    assert (st.engine, tok, reasons) == ("scan", tokenizer, [])
    g_ref, _ = _seq(monkeypatch, path)
    _assert_graphs_identical(g, g_ref)


def test_scanner_env_override(tmp_path, monkeypatch):
    path = _write_synth(tmp_path, 300)
    for off in ("0", "off", "FALSE", "no"):
        monkeypatch.setenv(SCANNER_ENV, off)
        assert not scanner_enabled()
        assert try_scan_ingest(path) is None
        _, st = ingest_trace_with_stats(path)
        assert st.engine == "stream"
    monkeypatch.setenv(SCANNER_ENV, "1")
    assert scanner_enabled()
    _, st = ingest_trace_with_stats(path)
    # without a C compiler there is no tokenizer to scan with
    assert st.engine == ("stream" if scan._native_tokenizer() is None
                         else "scan")


def test_scanner_size_heuristic(tmp_path, monkeypatch, tokenizer):
    """No size decides the engine: the default dispatch and
    `REPRO_TRACE_SCANNER=1` both scan, bit-identical to the stream
    engine, and `=off` overrides both."""
    path = _write_synth(tmp_path, 400, seed=11)
    g_stream, _ = _seq(monkeypatch, path)
    for state in (None, "1", "force"):
        if state is None:
            monkeypatch.delenv(SCANNER_ENV, raising=False)
        else:
            monkeypatch.setenv(SCANNER_ENV, state)
        g, st, tok, reasons = _traced(path)
        assert (st.engine, tok, reasons) == ("scan", tokenizer, []), state
        _assert_graphs_identical(g, g_stream)
    monkeypatch.setenv(SCANNER_ENV, "off")
    assert not scanner_enabled()
    _, st = ingest_trace_with_stats(path)
    assert st.engine == "stream"


def test_scanner_fallback_cases(tmp_path, tokenizer):
    """Everything outside the scanner's strict subset runs sequentially
    — same graph, `engine="stream"`, sequential diagnostics."""
    path = _write_synth(tmp_path, 300, seed=9)
    lines = open(path).read().splitlines()
    # iterable sources never scan
    _, st = ingest_trace_with_stats(lines)
    assert st.engine == "stream"
    # on_error="skip" and cfg validation are sequential-only
    _, st = ingest_trace_with_stats(path, on_error="skip")
    assert st.engine == "stream"
    # callable weight models may be stateful: per-unique eval is unsound
    _, st = ingest_trace_with_stats(path, weight_model=lambda o, t, b: 1.0)
    assert st.engine == "stream"
    # pretty-printed JSON (whitespace outside strings) falls back, and
    # the sequential interpreter accepts it
    pretty = tmp_path / "pretty.ndjson"
    pretty.write_text('{"fn": "f", "bb": "b0", "op": "add", '
                      '"def": "v0", "uses": []}\n')
    g, st = ingest_trace_with_stats(str(pretty))
    assert st.engine == "stream" and g.n == 1
    # malformed input: the scanner falls back whole-file, so the error
    # (and its line number) is exactly the sequential interpreter's
    bad = tmp_path / "bad.ndjson"
    bad.write_text(lines[0] + "\n" + '{"fn":"f","bb":"b0","uses":[]}\n')
    with pytest.raises(TraceFormatError, match="line 2"):
        ingest_trace_with_stats(str(bad))


_GOOD = ('{"fn":"f","bb":"b0","pp":"f:b0:i%d","op":"add","def":"v%d",'
         '"uses":["v%d"],"def_ty":"i32","use_tys":["i32"]}')
# one edit each that takes a trace outside the scanner's subset
_OUTSIDE = {
    "escaped_string": lambda ls: ls[:2] + [ls[2].replace('"add"',
                                                       '"a\\"dd"')] + ls[3:],
    "whitespace": lambda ls: ls[:2] + [ls[2].replace(',"op"', ', "op"')]
    + ls[3:],
    "unknown_key": lambda ls: ls[:2] + [ls[2][:-1] + ',"note":"x"}']
    + ls[3:],
    "kind_line": lambda ls: ls[:2]
    + ['{"kind":"block","fn":"f","bb":"b0","succs":[]}'] + ls[2:],
    "over_wide_token": lambda ls: ls[:2]
    + [ls[2].replace('"add"', '"' + "a" * 25 + '"')] + ls[3:],
    "crlf_line_end": lambda ls: ls[:2] + [ls[2] + "\r"] + ls[3:],
    "stray_byte": lambda ls: ls[:2] + [ls[2] + "#"] + ls[3:],
    # records the interpreter refuses for what they say
    "out_of_order_pp": lambda ls: ls[:3] + [ls[3].replace(":i3", ":i1")]
    + ls[4:],
    "pp_not_fn_bb": lambda ls: ls[:2] + [ls[2].replace('f:b0:i2', 'f:b1:i2')]
    + ls[3:],
    "use_tys_not_parallel": lambda ls: ls[:2] + [
        ls[2].replace('"use_tys":["i32"]', '"use_tys":["i32","i8"]')] + ls[3:],
    # malformed lines whose bytes a count of brackets and commas accepts
    "list_and_string_swapped": lambda ls: ls[:2] + [
        ls[2].replace('"fn":"f"', '"fn":["f"]')
        .replace('"uses":["v2"]', '"uses":"v2"')] + ls[3:],
    "key_inside_list": lambda ls: ls[:2] + [
        ls[2].replace('"uses":["v2"],', '"uses":["v2",')
        .replace('"use_tys":["i32"]}', '"use_tys":["i32"]]}')] + ls[3:],
}


@pytest.mark.parametrize("case", list(_OUTSIDE))
def test_scanner_falls_back_whole_file(tmp_path, monkeypatch, tokenizer,
                                       case):
    """Each input outside the subset runs through the sequential
    interpreter for the whole file: its graph where it accepts the file,
    and its exact error, line and message, where a later record is bad."""
    lines = _OUTSIDE[case]([_GOOD % (i, i + 1, i) for i in range(5)])
    path = tmp_path / "t.ndjson"
    path.write_text("\n".join(lines) + "\n")
    try:
        want = _seq(monkeypatch, str(path), keep_labels=True)
    except TraceFormatError as e:
        want = e
    if isinstance(want, TraceFormatError):
        with pytest.raises(TraceFormatError) as got:
            _traced(str(path), keep_labels=True)
        assert str(got.value) == str(want)
    else:
        g, st, tok, reasons = _traced(str(path), keep_labels=True)
        assert st.engine == "stream" and reasons == ["structure"]
        _assert_graphs_identical(g, want[0])
        assert st.summary() == want[1].summary()
    # a bad record after the edit: the interpreter's diagnostic, exactly
    path.write_text("\n".join(lines) + '\n{"fn":"f","bb":"b0","uses":[]}\n')
    with pytest.raises(TraceFormatError) as want:
        _seq(monkeypatch, str(path))
    with pytest.raises(TraceFormatError) as got:
        ingest_trace_with_stats(str(path))
    assert str(got.value) == str(want.value)
    assert got.value.lineno == want.value.lineno


# line ends the scanner accepts: the last record may end the input, and
# blank lines count as lines
_LINE_ENDS = {
    "no_trailing_newline": lambda ls: "\n".join(ls),
    "blank_lines": lambda ls: "\n".join(ls[:2] + [""] + ls[2:]) + "\n\n",
    "blank_then_no_newline": lambda ls: "\n\n".join(ls),
}


@pytest.mark.parametrize("case", list(_LINE_ENDS))
def test_scanner_line_ends(tmp_path, monkeypatch, tokenizer, case):
    """Line ends inside the subset scan, with the interpreter's graph and
    stats; a bad last record there is the interpreter's error."""
    lines = [_GOOD % (i, i + 1, i) for i in range(5)]
    path = tmp_path / "t.ndjson"
    path.write_text(_LINE_ENDS[case](lines))
    g_ref, st_ref = _seq(monkeypatch, str(path), keep_labels=True)
    g, st, tok, reasons = _traced(str(path), keep_labels=True)
    assert (st.engine, tok, reasons) == ("scan", tokenizer, [])
    _assert_graphs_identical(g, g_ref)
    sa, sb = st.summary(), st_ref.summary()
    for k in ("engine", "peak_chunk_edges"):
        sa.pop(k), sb.pop(k)
    assert sa == sb
    path.write_text(_LINE_ENDS[case](lines + ['{"fn":"f","bb":"b0","uses":[]}']))
    with pytest.raises(TraceFormatError) as want:
        _seq(monkeypatch, str(path))
    with pytest.raises(TraceFormatError) as got:
        ingest_trace_with_stats(str(path))
    assert str(got.value) == str(want.value)
    assert got.value.lineno == want.value.lineno


def test_native_build_failure(tmp_path, monkeypatch):
    """Where the native tokenizer cannot be built, the interpreter parses
    every file, whatever its size, and one `native_unavailable` fallback
    event says so."""
    from repro.core import _native
    monkeypatch.setattr(_native, "_LIBS", {})
    monkeypatch.setattr(_native, "_build", lambda src: None)
    monkeypatch.setattr(scan, "_native_unavailable_noted", False)
    monkeypatch.delenv(SCANNER_ENV, raising=False)
    path = _write_synth(tmp_path, 400, seed=2)
    g_ref, _ = _seq(monkeypatch, path)
    with obs.scoped(merge=False) as col:
        for _ in range(2):
            g, st = ingest_trace_with_stats(path)
            assert st.engine == "stream"
            _assert_graphs_identical(g, g_ref)
    assert not [e for e in col.events
                if e["name"] == "trace.ingest" and e["ph"] == "X"
                and e["args"]["engine"] == "scan"]
    reasons = [e["args"]["reason"] for e in col.events
               if e["name"] == "trace.scan_fallback"]
    assert reasons == ["native_unavailable"]


def test_native_tokenizer_built_lazily(tmp_path):
    """Importing the trace package builds nothing, and a `.npz` load (no
    NDJSON to tokenize) never builds the native tokenizer."""
    import subprocess
    import sys
    g = IRGraph(n=3, src=[0, 1], dst=[1, 2], w=[1.0, 2.0])
    npz = tmp_path / "g.npz"
    g.save_npz(str(npz))
    code = ("import sys; from repro.core import _native; "
            "from repro.trace import load_graph; "
            "assert not _native._LIBS, _native._LIBS; "
            "g = load_graph(sys.argv[1]); "
            "assert g.num_edges == 2 and not _native._LIBS, _native._LIBS")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")]
        + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    subprocess.run([sys.executable, "-c", code, str(npz)], check=True,
                   env=env, timeout=120)


# ---------------------------------------------------------------------- #
# binary container: round trip + universal acceptance
# ---------------------------------------------------------------------- #
def test_binary_round_trip_multichunk(tmp_path, monkeypatch):
    path = _write_synth(tmp_path, 2000, seed=1)
    g0, st0 = _seq(monkeypatch, path, keep_labels=True)
    rtb = tmp_path / "t.rtb"
    nchunks = write_trace_bin(rtb, g0, st0, chunk_edges=500)
    assert nchunks == -(-g0.num_edges // 500) and nchunks > 1
    g, st = read_trace_bin(rtb, keep_labels=True)
    _assert_graphs_identical(g, g0)
    assert g.name == g0.name
    assert st.engine == "binary"
    assert st.records == st0.records and st.functions == st0.functions
    # header inspect + chunk iteration agree with the full read
    hdr = read_trace_bin_header(rtb)
    assert hdr["n"] == g0.n and hdr["edges"] == g0.num_edges
    assert [c["edges"] for c in hdr["chunks"]] == \
        [500] * (nchunks - 1) + [g0.num_edges - 500 * (nchunks - 1)]
    parts = list(iter_trace_bin_chunks(rtb))
    assert len(parts) == nchunks
    assert np.array_equal(np.concatenate([p[1] for p in parts]), g0.src)
    assert np.array_equal(np.concatenate([p[3] for p in parts]), g0.w)


def test_binary_empty_trace_round_trips(tmp_path):
    g0 = IRGraph(n=0, src=[], dst=[], w=[], name="empty")
    rtb = tmp_path / "e.rtb"
    assert write_trace_bin(rtb, g0) == 0
    g, st = read_trace_bin(rtb)
    assert g.n == 0 and g.num_edges == 0 and st.engine == "binary"
    (hdr, s, d, w), = iter_trace_bin_chunks(rtb)
    assert hdr["edges"] == 0 and len(s) == len(d) == len(w) == 0


def test_binary_gzip_container(tmp_path, monkeypatch):
    path = _write_synth(tmp_path, 600, seed=4)
    g0, st0 = _seq(monkeypatch, path)
    rtb = tmp_path / "t.rtb.gz"
    assert is_binary_trace_path(rtb) and is_binary_trace_path("x.rtb.zst")
    assert not is_binary_trace_path("x.ndjson.gz")
    write_trace_bin(rtb, g0, st0)
    g, st = read_trace_bin(rtb)
    _assert_graphs_identical(g, g0)
    assert st.engine == "binary"


def test_binary_accepted_everywhere(tmp_path, capsys):
    """`.rtb` paths work wherever NDJSON paths do: ingest, load_graph,
    coerce_graph / run_pipeline, the CLI, and `repro.dist`."""
    from repro.trace.__main__ import main
    path = _write_synth(tmp_path, 800, seed=2)
    rtb = str(tmp_path / "t.rtb")
    assert main(["convert", path, rtb]) == 0
    g0, _ = ingest_trace_with_stats(path)
    g, st = ingest_trace_with_stats(rtb)
    assert st.engine == "binary"
    _assert_graphs_identical(g, g0)
    _assert_graphs_identical(load_graph(rtb), g0)
    part_j, _, rep_j = run_pipeline(path, 4, "wb_libra")
    part_b, _, rep_b = run_pipeline(rtb, 4, "wb_libra")
    assert np.array_equal(part_j.assignment, part_b.assignment)
    assert rep_j.exec_time == rep_b.exec_time
    assert main(["inspect", rtb]) == 0
    out = capsys.readouterr().out
    assert '"engine": "binary"' in out
    assert main(["partition", rtb, "-p", "4"]) == 0


def test_binary_dist_workers_identical(tmp_path):
    """`backend="dist"` on a `.rtb` source loads the conversion-time graph
    for any worker count, so workers=1 is bit-identical to "fast"."""
    from repro.dist import dist_ingest_with_stats
    path = _write_synth(tmp_path, 700, seed=6)
    rtb = str(tmp_path / "t.rtb")
    g0, st0 = ingest_trace_with_stats(path)
    write_trace_bin(rtb, g0, st0)
    for workers in (1, 3):
        gd, sd = dist_ingest_with_stats(rtb, workers=workers)
        assert sd.engine == "binary"
        _assert_graphs_identical(gd, g0)
    part_f, _, rep_f = run_pipeline(rtb, 8, "wb_libra", backend="fast")
    part_d, _, rep_d = run_pipeline(rtb, 8, "wb_libra", backend="dist",
                                    workers=1)
    assert np.array_equal(part_f.assignment, part_d.assignment)
    assert rep_f.exec_time == rep_d.exec_time


def test_binary_rejects_cfg(tmp_path):
    from repro.dist import dist_ingest_with_stats
    g0 = IRGraph(n=2, src=[0], dst=[1], w=[1.0])
    rtb = str(tmp_path / "t.rtb")
    write_trace_bin(rtb, g0)
    cfg = ['{"kind":"block","fn":"f","bb":"b0","succs":[]}']
    with pytest.raises(ValueError, match="cfg validation"):
        ingest_trace_with_stats(rtb, cfg=cfg)
    with pytest.raises(ValueError, match="cfg validation"):
        dist_ingest_with_stats(rtb, workers=2, cfg=cfg)


# ---------------------------------------------------------------------- #
# binary container: malformed inputs raise BinaryFormatError
# ---------------------------------------------------------------------- #
def _make_rtb(tmp_path, name="m.rtb", labels=False):
    g = IRGraph(n=3, src=[0, 1, 2, 0], dst=[1, 2, 0, 2],
                w=[1.0, 2.5, 3.0, 0.5],
                node_labels=["a", "b", "a"] if labels else None)
    p = tmp_path / name
    write_trace_bin(p, g, chunk_edges=3)
    return p, p.read_bytes()


def _rewrite_header(raw: bytes, mutate) -> bytes:
    """Re-serialize `raw` with its JSON header passed through `mutate`."""
    version, hlen = struct.unpack("<HI", raw[8:14])
    header = json.loads(raw[14:14 + hlen])
    mutate(header)
    hdr = json.dumps(header, separators=(",", ":")).encode()
    return raw[:8] + struct.pack("<HI", version, len(hdr)) + hdr \
        + raw[14 + hlen:]


def test_binary_bad_magic(tmp_path):
    p, raw = _make_rtb(tmp_path)
    p.write_bytes(b"NOTMAGIC" + raw[8:])
    with pytest.raises(BinaryFormatError, match="bad magic"):
        read_trace_bin(p)
    # an empty file is also "bad magic", not an index error
    p.write_bytes(b"")
    with pytest.raises(BinaryFormatError, match="bad magic"):
        read_trace_bin_header(p)


def test_binary_unsupported_version(tmp_path):
    p, raw = _make_rtb(tmp_path)
    p.write_bytes(raw[:8] + struct.pack("<H", BINARY_VERSION + 1) + raw[10:])
    with pytest.raises(BinaryFormatError, match="unsupported format version"):
        read_trace_bin(p)


def test_binary_truncated_chunk(tmp_path):
    p, raw = _make_rtb(tmp_path)
    p.write_bytes(raw[:-5])
    with pytest.raises(BinaryFormatError, match="truncated chunk"):
        read_trace_bin(p)
    # truncation inside the header is caught too
    p.write_bytes(raw[:20])
    with pytest.raises(BinaryFormatError, match="truncated header"):
        read_trace_bin(p)


def test_binary_dtype_mismatch(tmp_path):
    p, raw = _make_rtb(tmp_path)

    def swap(h):
        h["dtypes"]["w"] = "<f4"
    p.write_bytes(_rewrite_header(raw, swap))
    with pytest.raises(BinaryFormatError, match="dtype mismatch.*'w'"):
        read_trace_bin(p)


def test_binary_header_integrity(tmp_path):
    p, raw = _make_rtb(tmp_path)

    def lie(h):
        h["chunks"][0]["edges"] += 1
    p.write_bytes(_rewrite_header(raw, lie))
    with pytest.raises(BinaryFormatError, match="chunk table sums"):
        read_trace_bin(p)

    def drop(h):
        del h["edges"]
    p.write_bytes(_rewrite_header(raw, drop))
    with pytest.raises(BinaryFormatError, match="missing field 'edges'"):
        read_trace_bin(p)
    _, hlen = struct.unpack("<HI", raw[8:14])
    p.write_bytes(raw[:14] + b"x" * hlen + raw[14 + hlen:])
    with pytest.raises(BinaryFormatError, match="not valid JSON"):
        read_trace_bin(p)


def test_binary_label_id_out_of_range(tmp_path):
    p, raw = _make_rtb(tmp_path, labels=True)
    p.write_bytes(raw[:-4] + struct.pack("<i", 999))
    with pytest.raises(BinaryFormatError, match="label id 999 outside"):
        read_trace_bin(p, keep_labels=True)


def test_binary_endpoint_out_of_range(tmp_path):
    p, raw = _make_rtb(tmp_path)

    def shrink(h):
        h["n"] = 1
    p.write_bytes(_rewrite_header(raw, shrink))
    with pytest.raises(BinaryFormatError, match="endpoint exceeds"):
        read_trace_bin(p)


# ---------------------------------------------------------------------- #
# property test: convert -> ingest round trip (hypothesis, soft dep)
# ---------------------------------------------------------------------- #
def test_binary_round_trip_property(tmp_path):
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property test needs the hypothesis package")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=40, deadline=None,
              suppress_health_check=[HealthCheck.function_scoped_fixture])
    @given(data=st.data())
    def round_trip(data):
        n = data.draw(st.integers(min_value=1, max_value=50))
        m = data.draw(st.integers(min_value=0, max_value=200))
        ids = st.integers(min_value=0, max_value=n - 1)
        src = data.draw(st.lists(ids, min_size=m, max_size=m))
        dst = data.draw(st.lists(ids, min_size=m, max_size=m))
        w = data.draw(st.lists(
            st.floats(allow_nan=False, allow_infinity=False, width=64),
            min_size=m, max_size=m))
        labels = data.draw(st.one_of(st.none(), st.lists(
            st.text(max_size=6), min_size=n, max_size=n)))
        chunk = data.draw(st.integers(min_value=1, max_value=64))
        g0 = IRGraph(n=n, src=src, dst=dst, w=w, name="prop",
                     node_labels=list(labels) if labels else None)
        p = tmp_path / "prop.rtb"
        write_trace_bin(p, g0, chunk_edges=chunk)
        g1, st1 = read_trace_bin(p, keep_labels=True)
        assert st1.engine == "binary"
        assert g1.n == n and g1.name == "prop"
        assert np.array_equal(g1.src, g0.src)
        assert np.array_equal(g1.dst, g0.dst)
        assert np.array_equal(g1.w, g0.w)      # exact float64 round trip
        assert (g1.node_labels == (list(labels) if labels else None))

    round_trip()


# ---------------------------------------------------------------------- #
# the 10x ingestion gate (binary fast path vs streaming JSON)
# ---------------------------------------------------------------------- #
def test_binary_read_is_10x_faster_than_json(tmp_path, monkeypatch):
    """The tentpole's acceptance gate, asserted in-tree on a small trace:
    reading the converted `.rtb` must beat sequential JSON ingestion by
    >= 10x edges/s on identical output.  (benchmarks/trace_ingest.py
    gates the full 1M-line version; binary loads are ~100x+ even here,
    so the margin absorbs machine noise.)"""
    import time
    path = _write_synth(tmp_path, 20_000, seed=0)
    t0 = time.perf_counter()
    g_json, _ = _seq(monkeypatch, path)
    t_json = time.perf_counter() - t0
    rtb = tmp_path / "t.rtb"
    write_trace_bin(rtb, g_json)
    t_bin = min(_timed(read_trace_bin, rtb) for _ in range(3))
    g_bin, _ = read_trace_bin(rtb)
    _assert_graphs_identical(g_bin, g_json)
    assert t_json / t_bin >= 10.0, \
        f"binary speedup {t_json / t_bin:.1f}x < 10x gate"


def _timed(fn, *args):
    import time
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0
