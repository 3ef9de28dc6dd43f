"""The paper's CNN benchmark program (arXiv:2010.04414, Table 3).

A copy of `_cnn` and its layers from the program's `core/benchgraphs.py`:
conv 3x3 (1 -> c1 channels), 2x2 max pool, conv 3x3 (c1 -> c2), 2x2 max
pool, and a fully connected layer to 10 outputs, traced operation by
operation.  At the paper's input (28x28 image, 6 and 12 channels) the
graph has 760,083 vertices and 1,242,234 edges.  The data seed draws
the image and the weights; it changes values, not the graph, because no
branch of the program depends on them.
"""
from __future__ import annotations

import numpy as np

from tracer import Tracer


SUFFIX = ".npz"


def write(config: dict, path: str) -> None:
    """Trace the CNN at the configuration's sizes and data seed and write
    the `.npz` snapshot the plan service loads (the program's
    `IRGraph.save_npz` keys) to `path`."""
    t = Tracer("cnn/paper")
    _cnn(t, seed=config["data_seed"], **config["sizes"])
    with open(path, "wb") as f:
        np.savez_compressed(f, **t.graph())


def _matmul_fc(t: Tracer, x: list, w_np: np.ndarray, relu: bool) -> list:
    n_in, n_out = w_np.shape
    wmem = t.alloca(n_in * n_out)
    for i in range(n_in):
        for j in range(n_out):
            t.store(wmem, i * n_out + j, t.const(float(w_np[i, j])))
    out = t.alloca(n_out)
    for j in range(n_out):
        acc = t.const(0.0)
        for i in range(n_in):
            acc = t.bin("+", acc,
                        t.bin("*", t.load(x, i), t.load(wmem, i * n_out + j)))
        if relu:
            acc = t.un("relu", acc)
        t.store(out, j, acc)
    return out


def _conv2d(t: Tracer, img: list, h: int, w: int, cin: int, cout: int,
            kern_np: np.ndarray) -> tuple[list, int, int]:
    kh = kw = kern_np.shape[2]
    oh, ow = h - kh + 1, w - kw + 1
    kern = t.alloca(cout * cin * kh * kw)
    for idx, val in enumerate(kern_np.ravel()):
        t.store(kern, idx, t.const(float(val)))
    out = t.alloca(cout * oh * ow)
    for co in range(cout):
        for i in range(oh):
            for j in range(ow):
                acc = t.const(0.0)
                for ci in range(cin):
                    for ki in range(kh):
                        for kj in range(kw):
                            px = t.load(img, ci * h * w + (i + ki) * w + (j + kj))
                            kv = t.load(kern, ((co * cin + ci) * kh + ki) * kw + kj)
                            acc = t.bin("+", acc, t.bin("*", px, kv))
                t.store(out, co * oh * ow + i * ow + j, t.un("relu", acc))
    return out, oh, ow


def _pool2(t: Tracer, img: list, c: int, h: int, w: int
           ) -> tuple[list, int, int]:
    oh, ow = h // 2, w // 2
    out = t.alloca(c * oh * ow)
    for ci in range(c):
        for i in range(oh):
            for j in range(ow):
                a = t.load(img, ci * h * w + 2 * i * w + 2 * j)
                b = t.load(img, ci * h * w + 2 * i * w + 2 * j + 1)
                cc = t.load(img, ci * h * w + (2 * i + 1) * w + 2 * j)
                d = t.load(img, ci * h * w + (2 * i + 1) * w + 2 * j + 1)
                t.store(out, ci * oh * ow + i * ow + j,
                        t.bin("max", t.bin("max", a, b), t.bin("max", cc, d)))
    return out, oh, ow


def _cnn(t: Tracer, img_side: int, c1: int = 6, c2: int = 12,
         seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    img = t.alloca(img_side * img_side)
    for i in range(img_side * img_side):
        t.store(img, i, t.const(float(rng.standard_normal())))
    x, h, w = _conv2d(t, img, img_side, img_side, 1, c1,
                      rng.standard_normal((c1, 1, 3, 3)) * 0.1)
    x, h, w = _pool2(t, x, c1, h, w)
    x, h, w = _conv2d(t, x, h, w, c1, c2,
                      rng.standard_normal((c2, c1, 3, 3)) * 0.1)
    x, h, w = _pool2(t, x, c2, h, w)
    _matmul_fc(t, x, rng.standard_normal((c2 * h * w, 10)) * 0.1, relu=False)
