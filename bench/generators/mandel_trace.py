"""The paper's Mandel benchmark program (arXiv:2010.04414, Table 3) as a
TRACE_SCHEMA v0 NDJSON trace.

`_mandel` is a copy of the program's `core/benchgraphs.py` builder, with
the basic block of each traced operation marked: `entry` (the output
buffer's alloca), `pixel` (a point's coordinates and the zero start of
z), `iter` (one escape-time iteration, re-entered every iteration) and
`exit` (the iteration count stored).  `RecordingTracer` runs it on the
benchmark's copy of `Tracer` and writes one instruction record per traced
operation, in execution order:

* every record defines a value `v<vertex>`, and lists as `uses` the
  vertices its graph edges come from, in edge order, so ingesting the
  trace gives the traced graph's vertices and edges exactly;
* a store defines a memory version that later loads of the cell use
  (memory SSA): the program's RAW dependency edges;
* `def_ty` is the value's LLVM type (`double` for values, `ptr` for
  addresses, `i1` for compares), so under the `bytes` weight model an
  edge weighs the bytes of the value it moves.

At the paper's input (4092 points: a 63 x 63 grid, at most 24
iterations) the trace has 308,080 records and ingests to 308,080
vertices and 512,361 edges.  No value is drawn at random.
`test_generators.py` checks the parsed graph against the program's own
`build_graph("mandel", "paper")`.
"""
from __future__ import annotations

import json
import math

import numpy as np

from tracer import Tracer


SUFFIX = ".ndjson"

_BIN = {"+": ("fadd", "double"), "-": ("fsub", "double"),
        "*": ("fmul", "double"), "/": ("fdiv", "double"),
        "<": ("fcmp", "i1"), "max": ("call", "double")}


class RecordingTracer(Tracer):
    """`Tracer` that also keeps each vertex's opcode, type and basic
    block, and writes the run as TRACE_SCHEMA v0 records."""

    def __init__(self, name: str, fn: str):
        super().__init__(name)
        self.fn = fn
        self.bb = "entry"
        self.pp = 0
        self.records: list = []      # (op, def_ty, bb, pp) per vertex

    def enter(self, bb: str) -> None:
        """Start (or re-enter) basic block `bb`."""
        self.bb, self.pp = bb, 0

    def _node(self) -> int:
        self.records.append((self._op, self._ty, self.bb, self.pp))
        self.pp += 1
        return super()._node()

    def _mark(self, op: str, ty: str) -> None:
        self._op, self._ty = op, ty

    def const(self, val):
        self._mark("const", "double")
        return super().const(val)

    def bin(self, op: str, a, b):
        self._mark(*_BIN[op])
        return super().bin(op, a, b)

    def un(self, op: str, a):
        self._mark("fneg" if op == "neg" else "call", "double")
        return super().un(op, a)

    def alloca(self, n: int, init=0.0):
        self._mark("alloca", "ptr")
        return super().alloca(n, init)

    def _gep(self, mem) -> int:
        op, ty = self._op, self._ty
        self._mark("getelementptr", "ptr")
        gep = super()._gep(mem)
        self._mark(op, ty)
        return gep

    def load(self, mem, i: int):
        self._mark("load", "double")
        return super().load(mem, i)

    def store(self, mem, i: int, val) -> None:
        self._mark("store", "double")
        super().store(mem, i, val)

    def lines(self):
        """The trace, one NDJSON record per vertex."""
        g = self.graph()
        src, dst = g["src"], g["dst"]
        if np.any(np.diff(dst) < 0):
            raise ValueError("edges are not grouped by destination")
        starts = np.searchsorted(dst, np.arange(g["n"] + 1))
        fn = self.fn
        for v, (op, ty, bb, pp) in enumerate(self.records):
            uses = [f"v{s}" for s in src[starts[v]:starts[v + 1]].tolist()]
            yield json.dumps(
                {"fn": fn, "bb": bb, "pp": f"{fn}:{bb}:i{pp}", "op": op,
                 "def": f"v{v}", "uses": uses, "def_ty": ty},
                separators=(",", ":"))


def write(config: dict, path: str) -> None:
    """Trace Mandel at the configuration's sizes and write the NDJSON
    trace to `path`."""
    t = RecordingTracer("mandel/paper", "mandel")
    _mandel(t, **config["sizes"])
    with open(path, "w", encoding="utf-8") as f:
        for line in t.lines():
            f.write(line + "\n")


def _mandel(t: RecordingTracer, npoints: int, max_iter: int = 24) -> None:
    side = int(math.sqrt(npoints))
    t.enter("entry")
    out = t.alloca(side * side)
    for i in range(side):
        for j in range(side):
            t.enter("pixel")
            cre = t.const(-2.0 + 3.0 * i / side)
            cim = t.const(-1.5 + 3.0 * j / side)
            zr, zi = t.const(0.0), t.const(0.0)
            it = 0
            while it < max_iter:
                t.enter("iter")
                zr2 = t.bin("*", zr, zr)
                zi2 = t.bin("*", zi, zi)
                mag = t.bin("+", zr2, zi2)
                if mag[1] > 4.0:
                    break
                nzr = t.bin("+", t.bin("-", zr2, zi2), cre)
                zi = t.bin("+", t.bin("*", t.bin("*", t.const(2.0), zr), zi),
                           cim)
                zr = nzr
                it += 1
            t.enter("exit")
            t.store(out, i * side + j, t.const(float(it)))
