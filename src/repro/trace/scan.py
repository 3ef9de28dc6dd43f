"""Structural-index NDJSON scanner (the fast JSON path).

Instead of `json.loads` per line, the scanner treats the whole byte
stream as data.  A native tokenizer (`_scan.c`, built by `core._native`
on the first scan and cached) makes one forward pass over the bytes and
turns them into integer columns — per record its op, def symbol and def
type; per use its record, symbol and type — with every token interned
per class by a hash table.  The rolling def-table semantics of
`ingest._StreamBuilder` are then replayed with one stable lexsort over
(symbol, time) events: a use binds to the latest def event before it in
its group, a group-leading use of a non-`const:` symbol materialises
(and registers) a live-in, and `const:` uses with no preceding def
materialise fresh vertices.  Edge weights are evaluated once per unique
`(op, use_ty, producer_bytes)` triple and gathered, so float results are
bit-identical to calling the weight model per edge.

The scanner is *strict and partial*: it accepts only the compact,
machine-written TRACE_SCHEMA v0 subset (no escapes, no whitespace
outside strings, every record carrying fn/bb/pp/op/def/uses on its own
line, tokens within fixed width bounds), which the tokenizer parses
exactly.  Anything else — CFG `kind` lines, `on_error="skip"`,
iterable/file-like sources, pretty-printed JSON, unknown keys, a
malformed byte — falls back to the sequential interpreter, which is the
semantic reference and owns all error reporting.  Fallback is
whole-file, so diagnostics (line numbers, messages) are exactly the
sequential path's.  Where the tokenizer cannot be built (no C compiler,
``REPRO_NO_NATIVE``) every file takes the sequential interpreter.

Set ``REPRO_TRACE_SCANNER=0`` (or ``off``) to disable the scanner
everywhere.
"""
from __future__ import annotations

import ctypes
import os
from time import perf_counter
from typing import NamedTuple

import numpy as np

from .. import obs
from ..core.graph import IRGraph
from .schema import type_bytes
from .weights import resolve_weight_model

__all__ = ["SCANNER_ENV", "scanner_enabled", "try_scan_ingest"]

SCANNER_ENV = "REPRO_TRACE_SCANNER"

_SCAN_C = os.path.join(os.path.dirname(__file__), "_scan.c")
# `_scan.c`'s info[] slots, its columns (record op/def/def_ty, use
# record/symbol/type, then each table's offsets and lengths) and tables
_I_STATUS, _I_LINES, _I_VOID, _I_RECORDS, _I_USES, _I_TAB0 = range(6)
_N_VEC = 6
_T_FN, _T_BB, _T_OP, _T_TY, _T_SYM = range(5)
_N_TAB = 5
_native_unavailable_noted = False


class _Fallback(Exception):
    """Input outside the scanner's subset — use the sequential path."""


def scanner_enabled() -> bool:
    """False where ``REPRO_TRACE_SCANNER`` is 0/off/false/no."""
    return os.environ.get(SCANNER_ENV, "").lower() not in (
        "0", "off", "false", "no")


def _bind_scan(lib):
    lib.trace_scan.restype = ctypes.c_void_p
    lib.trace_scan.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                               np.ctypeslib.ndpointer(np.int64,
                                                      flags="C_CONTIGUOUS")]
    lib.trace_scan_take.restype = None
    lib.trace_scan_take.argtypes = [
        ctypes.c_void_p, ctypes.c_int32,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
    lib.trace_scan_free.restype = None
    lib.trace_scan_free.argtypes = [ctypes.c_void_p]
    return lib


def _native_tokenizer():
    """The loaded `_scan.c`, or None without a compiler; built on first
    use, so only a scan ever triggers the build."""
    from ..core._native import native_library
    lib = native_library(_SCAN_C, _bind_scan)
    global _native_unavailable_noted
    if lib is None and not _native_unavailable_noted and obs.enabled():
        obs.event("trace.scan_fallback", reason="native_unavailable")
        _native_unavailable_noted = True
    return lib


def try_scan_ingest(source, *, weight_model="bytes", on_error="raise",
                    cfg=None, name=None, keep_labels=False):
    """Scan `source` if eligible; return `(IRGraph, TraceStats)` or None.

    None means "not handled" — the caller runs the sequential ingester,
    which reproduces both the result and any error diagnostics.
    """
    if not scanner_enabled():
        return None
    if cfg is not None or on_error != "raise":
        obs.event("trace.scan_fallback", reason="cfg_or_on_error")
        return None
    if not isinstance(weight_model, str):
        # user callables may be stateful; the scanner evaluates weights
        # per unique triple, which is only sound for pure models
        obs.event("trace.scan_fallback", reason="weight_model_callable")
        return None
    if not isinstance(source, (str, os.PathLike)):
        obs.event("trace.scan_fallback", reason="not_a_path")
        return None
    lib = _native_tokenizer()
    if lib is None:
        return None
    try:
        data = _read_all(os.fspath(source))
    except (_Fallback, OSError):
        obs.event("trace.scan_fallback", reason="read_error")
        return None
    from .ingest import _source_name
    t0 = perf_counter()
    try:
        out = _scan_bytes(data, resolve_weight_model(weight_model),
                          keep_labels, _source_name(source, name), lib)
    except _Fallback:
        obs.event("trace.scan_fallback", reason="structure")
        return None
    if obs.enabled():
        t1 = perf_counter()
        m = int(out[0].num_edges)
        obs.complete("trace.ingest", t0, t1, engine="scan",
                     tokenizer="native", bytes=len(data), edges=m,
                     edges_per_s=round(m / max(t1 - t0, 1e-9)))
    return out


def _read_all(path: str) -> bytes:
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return f.read()
    if path.endswith((".zst", ".zstd")):
        try:
            import zstandard
        except ImportError:
            raise _Fallback from None      # sequential raises the real error
        with open(path, "rb") as fh:
            return zstandard.ZstdDecompressor().stream_reader(fh).read()
    with open(path, "rb") as f:
        return f.read()


def _unique_rows(cols):
    """(sort_order_repr, inverse, n_unique) for rows given as equal-length
    integer column arrays — a lexsort-based np.unique(axis=0)."""
    k = cols[0].shape[0]
    if k == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 0
    order = np.lexsort(tuple(reversed(cols)))
    new = np.zeros(k, np.bool_)
    new[0] = True
    for c in cols:
        cs = c[order]
        new[1:] |= cs[1:] != cs[:-1]
    uid_sorted = np.cumsum(new) - 1
    inverse = np.empty(k, np.int64)
    inverse[order] = uid_sorted
    repr_idx = order[new]
    return repr_idx, inverse, int(uid_sorted[-1]) + 1


def _decode(mv, start, length) -> str:
    return bytes(mv[start:start + length]).decode("utf-8")


# ---------------------------------------------------------------------- #
# the scan
# ---------------------------------------------------------------------- #
class _Tokens(NamedTuple):
    """What the tokenizer hands the replay.  Ids are dense per class in
    order of first appearance; the replay reads only their equality."""
    lines: int                  # lines read (blank lines included)
    void_defs: int              # records with def: null
    functions: int              # distinct fn
    blocks: int                 # distinct (fn, bb)
    rec_op: np.ndarray          # (R,) op id
    rec_def: np.ndarray         # (R,) symbol id of the def, -1 for null
    rec_defty: np.ndarray       # (R,) type id of def_ty, 0 where absent
    use_rec: np.ndarray         # (E,) record of each use, in trace order
    use_sym: np.ndarray         # (E,) symbol id (scoped by function)
    use_ty: np.ndarray          # (E,) type id of use_tys, 0 where absent
    ops: list                   # op id -> name
    types: list                 # type id -> name; types[0] is None
    sym_const: np.ndarray       # (S,) symbol starts with "const:"
    sym_off: np.ndarray         # (S,) a byte offset of the symbol's name
    sym_len: np.ndarray         # (S,) its length


def _scan_bytes(data: bytes, weight_fn, keep_labels: bool, name: str,
                lib):
    """The graph of `data` through the loaded tokenizer `lib`; raises
    `_Fallback` outside the subset."""
    from .ingest import TraceStats
    mv = np.frombuffer(data, np.uint8)
    if mv.shape[0] == 0:
        g = IRGraph(n=0, src=np.zeros(0, np.int32), dst=np.zeros(0, np.int32),
                    w=np.zeros(0, np.float64), name=name,
                    node_labels=[] if keep_labels else None)
        return g, TraceStats(engine="scan")
    return _replay(_native_tokens(lib, data, mv), mv, weight_fn,
                   keep_labels, name)


def _starts_with(mv, off, length, prefix: bytes) -> np.ndarray:
    ok = length >= len(prefix)
    last = mv.shape[0] - 1
    for j, ch in enumerate(prefix):
        ok &= mv[np.minimum(off + j, last)] == ch
    return ok


def _native_tokens(lib, data: bytes, mv) -> _Tokens:
    info = np.zeros(_I_TAB0 + _N_TAB, np.int64)
    ctx = lib.trace_scan(data, len(data), info)
    if not ctx:
        raise _Fallback                 # out of memory: stream instead
    try:
        if info[_I_STATUS]:
            raise _Fallback

        def take(column, size):
            out = np.empty(int(size), np.int32)
            lib.trace_scan_take(ctx, column, out)
            return out

        def table(t):
            size = info[_I_TAB0 + t]
            return (take(_N_VEC + 2 * t, size).astype(np.int64),
                    take(_N_VEC + 2 * t + 1, size).astype(np.int64))

        R, E = info[_I_RECORDS], info[_I_USES]
        rec_op, rec_def, rec_defty = (take(c, R) for c in range(3))
        use_rec, use_sym, use_ty = (take(c, E) for c in range(3, 6))
        op_off, op_len = table(_T_OP)
        ty_off, ty_len = table(_T_TY)
        sym_off, sym_len = table(_T_SYM)
    finally:
        lib.trace_scan_free(ctx)
    return _Tokens(
        lines=int(info[_I_LINES]), void_defs=int(info[_I_VOID]),
        functions=int(info[_I_TAB0 + _T_FN]),
        blocks=int(info[_I_TAB0 + _T_BB]),
        rec_op=rec_op, rec_def=rec_def, rec_defty=rec_defty,
        use_rec=use_rec, use_sym=use_sym, use_ty=use_ty,
        ops=[_decode(mv, o, n) for o, n in zip(op_off, op_len)],
        types=[None] + [_decode(mv, o, n) for o, n in zip(ty_off, ty_len)],
        sym_const=_starts_with(mv, sym_off, sym_len, b"const:"),
        sym_off=sym_off, sym_len=sym_len)


def _replay(t: _Tokens, mv, weight_fn, keep_labels: bool, name: str):
    """Rolling def-table semantics over the tokenized records."""
    from .ingest import TraceStats
    R, E = t.rec_op.shape[0], t.use_sym.shape[0]
    rec_of_use = t.use_rec.astype(np.int64)
    n_uses = np.bincount(rec_of_use, minlength=R)
    use_start = np.concatenate(([0], np.cumsum(n_uses)))[:-1]

    # ---- event binding ----------------------------------------------- #
    def_recs = np.flatnonzero(t.rec_def >= 0)
    D = def_recs.shape[0]
    ssym = np.concatenate((t.use_sym, t.rec_def[def_recs]))
    sym_is_const = t.sym_const

    ev_time = np.concatenate((2 * rec_of_use, 2 * def_recs + 1))
    ev_isdef = np.concatenate((np.zeros(E, np.bool_), np.ones(D, np.bool_)))
    ev_use = np.concatenate((np.arange(E), np.full(D, -1)))
    ev_rec = np.concatenate((rec_of_use, def_recs))
    order = np.lexsort((ev_time, ssym))
    s_sym = ssym[order]
    s_isdef = ev_isdef[order]
    s_use = ev_use[order]
    s_rec = ev_rec[order]
    N = order.shape[0]
    gs = np.ones(N, np.bool_)
    if N > 1:
        gs[1:] = s_sym[1:] != s_sym[:-1]
    s_const = sym_is_const[s_sym]
    eff = s_isdef | (gs & ~s_isdef & ~s_const)
    j = np.arange(N)
    P = np.maximum.accumulate(np.where(eff, j, -1))
    S = np.maximum.accumulate(np.where(gs, j, -1))
    is_use_ev = ~s_isdef
    bound = is_use_ev & ~eff & (P >= S)
    creator = is_use_ev & eff
    const_fresh = is_use_ev & ~eff & ~bound
    if (const_fresh & ~s_const).any():
        raise _Fallback                     # unreachable by construction

    fresh_sorted = creator | const_fresh
    fresh = np.zeros(E, np.bool_)
    fresh[s_use[fresh_sorted]] = True

    # ---- vertex numbering (record, then fresh uses, interleaved) ----- #
    cfx = np.concatenate(([0], np.cumsum(fresh)))   # exclusive prefix
    rec_vertex = np.arange(R) + cfx[use_start]
    fresh_slot = (rec_vertex[rec_of_use] + 1
                  + (cfx[np.arange(E)] - cfx[use_start[rec_of_use]]))
    n_total = R + int(cfx[-1])

    # ---- producers, pbytes, src/dst ---------------------------------- #
    ty_bytes = np.array([-1.0] + [type_bytes(s) for s in t.types[1:]])
    def_bytes = ty_bytes[t.rec_defty]
    prod = P[np.flatnonzero(bound)]
    bpos = np.flatnonzero(bound)
    prod_vert = np.where(s_isdef[prod], rec_vertex[s_rec[prod]],
                         fresh_slot[np.maximum(s_use[prod], 0)])
    prod_bytes = np.where(s_isdef[prod] & (def_bytes[s_rec[prod]] >= 0),
                          def_bytes[s_rec[prod]], -1.0)
    src = np.empty(E, np.int64)
    src[s_use[bpos]] = prod_vert
    src[fresh] = fresh_slot[fresh]
    pb = np.full(E, -1.0)
    pb[s_use[bpos]] = prod_bytes
    dst = rec_vertex[rec_of_use]

    # ---- weights: one call per unique (op, use_ty, pbytes) ----------- #
    op_of_use = t.rec_op[rec_of_use]
    w_repr, w_inv, nW = _unique_rows([op_of_use, t.use_ty,
                                      np.ascontiguousarray(pb).view(np.int64)])
    w_uniq = np.empty(nW)
    for u, i in enumerate(w_repr):
        p = pb[i]
        w_uniq[u] = weight_fn(t.ops[int(op_of_use[i])],
                              t.types[int(t.use_ty[i])],
                              None if p < 0 else float(p))
    w = w_uniq[w_inv]

    # ---- labels ------------------------------------------------------ #
    labels = None
    if keep_labels:
        lab = np.empty(n_total, object)
        lab[rec_vertex] = np.array(t.ops, object)[t.rec_op]
        cf_use = np.zeros(E, np.bool_)
        cf_use[s_use[const_fresh]] = True
        li_use = np.zeros(E, np.bool_)
        li_use[s_use[creator]] = True
        lab[fresh_slot[cf_use]] = "const"
        for e in np.flatnonzero(li_use).tolist():
            s = t.use_sym[e]
            lab[fresh_slot[e]] = _decode(mv, t.sym_off[s], t.sym_len[s])
        labels = list(lab)

    stats = TraceStats(
        lines=t.lines, records=R,
        const_uses=int(np.count_nonzero(const_fresh)),
        livein_uses=int(np.count_nonzero(creator)),
        void_defs=t.void_defs, functions=t.functions, blocks=t.blocks,
        engine="scan")
    g = IRGraph(n=n_total, src=src.astype(np.int32),
                dst=dst.astype(np.int32), w=w, name=name,
                node_labels=labels)
    return g, stats
