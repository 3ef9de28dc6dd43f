"""Dynamic-trace recorder for the paper's benchmark programs.

A copy of `Tracer` from the program's `core/benchgraphs.py`, kept with the
benchmark so that a change to the program cannot move the yardstick.
Every executed operation becomes a vertex; register uses and memory RAW
dependencies become edges, weighted by a reuse-distance cache model
(1, 4, 12 or 100 cycles).  `graph()` returns plain arrays instead of the
program's `IRGraph`.  `test_generators.py` checks the copy against the
program's own `build_graph`.
"""
from __future__ import annotations

import math

import numpy as np

# reuse-distance cache model: (threshold, cycles) — L1 hit, L2 hit, DRAM
_L1_WINDOW, _L1_T = 256, 4.0
_L2_WINDOW, _L2_T = 4096, 12.0
_DRAM_T = 100.0
_REG_T = 1.0  # register-register dependency weight


class _Mem:
    """An alloca'd region: base-pointer node + per-cell metadata."""

    __slots__ = ("base", "cells", "last_gep", "n_geps")

    def __init__(self, base: int, cells: list):
        self.base = base
        self.cells = cells
        self.last_gep = base
        self.n_geps = 0


class Tracer:
    """Dynamic-trace recorder: executes the program while building G.

    `gep_chain_period` controls address-computation structure: every K-th
    access re-anchors at the base pointer (direct indexing), intermediate
    ones chain off the previous gep (pointer-bump idiom).  K=1 gives the
    pure hub-and-spoke shape of the paper's Fig. 5 examples.
    """

    __slots__ = ("src", "dst", "w", "n_nodes", "clock", "name",
                 "gep_chain_period")

    def __init__(self, name: str, gep_chain_period: int = 1):
        self.name = name
        self.gep_chain_period = max(1, gep_chain_period)
        self.src: list[int] = []
        self.dst: list[int] = []
        self.w: list[float] = []
        self.n_nodes = 0
        self.clock = 0

    # -- node/edge primitives ------------------------------------------- #
    def _node(self) -> int:
        nid = self.n_nodes
        self.n_nodes = nid + 1
        self.clock += 1
        return nid

    def _edge(self, s: int, d: int, w: float) -> None:
        self.src.append(s)
        self.dst.append(d)
        self.w.append(w)

    # -- IR ops ----------------------------------------------------------#
    def const(self, val) -> tuple[int, float]:
        return (self._node(), val)

    def bin(self, op: str, a, b):
        """Arithmetic/compare: new node depending on both operands."""
        nid = self._node()
        self._edge(a[0], nid, _REG_T)
        self._edge(b[0], nid, _REG_T)
        x, y = a[1], b[1]
        if op == "+":
            v = x + y
        elif op == "-":
            v = x - y
        elif op == "*":
            v = x * y
        elif op == "/":
            v = x / y if y != 0 else 0.0
        elif op == "<":
            v = float(x < y)
        elif op == "max":
            v = x if x > y else y
        else:
            raise ValueError(op)
        return (nid, v)

    def un(self, op: str, a):
        nid = self._node()
        self._edge(a[0], nid, _REG_T)
        x = a[1]
        if op == "neg":
            v = -x
        elif op == "relu":
            v = x if x > 0 else 0.0
        elif op == "sqrt":
            v = math.sqrt(x) if x > 0 else 0.0
        else:
            raise ValueError(op)
        return (nid, v)

    def alloca(self, n: int, init=0.0):
        """A memory region.  Returns (base_ptr_node, cells) where each cell
        is [last_writer_node, value, last_access_clock].  The base pointer
        register is the LLVM-trace hub: every access computes an address
        from it via a `getelementptr` node (light register edges), which is
        what gives these graphs their power-law degree skew."""
        base = self._node()  # the alloca instruction itself
        return _Mem(base, [[base, init, self.clock] for _ in range(n)])

    def _mem_time(self, cell) -> float:
        age = self.clock - cell[2]
        if age < _L1_WINDOW:
            return _L1_T
        if age < _L2_WINDOW:
            return _L2_T
        return _DRAM_T

    def _gep(self, mem) -> int:
        """Address computation (`getelementptr`).  Compiled loops mix the
        pointer-bump idiom (gep chained off the previous gep) with direct
        indexing off the base pointer; we re-anchor to the base every 8th
        access, which reproduces both the gep chains and the moderate
        base-pointer hubs of real dynamic IR traces."""
        gep = self._node()
        anchor = (mem.base if mem.n_geps % self.gep_chain_period == 0
                  else mem.last_gep)
        self._edge(anchor, gep, _REG_T)
        mem.last_gep = gep
        mem.n_geps += 1
        return gep

    def load(self, mem, i: int):
        cell = mem.cells[i]
        t = self._mem_time(cell)
        gep = self._gep(mem)
        nid = self._node()
        self._edge(gep, nid, _REG_T)     # address -> load
        self._edge(cell[0], nid, t)      # RAW memory dependency, timed
        cell[2] = self.clock
        return (nid, cell[1])

    def store(self, mem, i: int, val) -> None:
        cell = mem.cells[i]
        t = self._mem_time(cell)
        gep = self._gep(mem)
        nid = self._node()
        self._edge(gep, nid, _REG_T)     # address -> store
        self._edge(val[0], nid, t)       # value into memory, timed
        cell[0] = nid
        cell[1] = val[1]
        cell[2] = self.clock

    def graph(self) -> dict:
        """The traced graph as plain arrays (the `.npz` snapshot's keys)."""
        return {"n": self.n_nodes, "src": np.array(self.src, np.int32),
                "dst": np.array(self.dst, np.int32),
                "w": np.array(self.w, np.float64), "name": self.name}
