"""DeepSeek-V3's training step as a weighted dataflow graph.

The architecture (config https://huggingface.co/deepseek-ai/DeepSeek-V3,
technical report arXiv:2412.19437) written the plain way in `jax.numpy`:
the forward pass, the next-token loss plus the multi-token-prediction
(MTP) loss (report §2.2, weight 0.3), and `jax.grad` of their sum,
recorded with `jax.make_jaxpr` over `ShapeDtypeStruct`s, so no weight is
ever allocated.

  * embedding, then `first_k_dense_replace` dense layers (MLA attention
    and a SwiGLU MLP of `intermediate_size`), then the MoE layers (MLA
    and `n_routed_experts` routed experts of `moe_intermediate_size`
    beside `n_shared_experts` shared ones), final RMSNorm and an untied
    output head;
  * MLA: queries through the `q_lora_rank` latent, keys and values
    through the `kv_lora_rank` latent, a `qk_rope_head_dim` RoPE part
    (shared by the heads for keys) beside the `qk_nope_head_dim` part,
    softmax scale 1/sqrt(qk_nope + qk_rope), causal;
  * routing: sigmoid scores in float32; the experts' choice adds the
    correction bias, keeps the `topk_group` of `n_group` groups with the
    largest sum of their two best scores, and takes the
    `num_experts_per_tok` best; their gates are the unbiased scores,
    normalised (`norm_topk_prob`) and scaled by `routed_scaling_factor`;
  * each routed expert takes the `expert_capacity` tokens with the
    largest gate for it (`lax.top_k`), gathers them, runs its SwiGLU,
    scales by the gate and scatter-adds into the layer's output, so every
    expert is vertices of its own;
  * MTP depth 1: RMSNorm of the main model's last hidden state and of
    the embedding of the next token, concatenated, projected back to the
    hidden size, one MoE block, a norm and the shared output head, which
    predicts the token after next.

The dense and the MoE layers are each traced once, as a `lax.scan` over
their stacked parameters, and the graph construction unrolls every scan
with no cap.  That construction is a copy of the program's
`core/jaxpr_graph.py` `jaxpr_to_graph`: one vertex per executed
primitive, an edge per def-use dependency weighted by the bytes of the
value (at least 1).  `bench/test_deepseek_v3_step.py` holds the copy to
the program's at a tiny size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend import core as jcore

SUFFIX = ".npz"


def write(config: dict, path: str) -> None:
    """Record the configuration's training step and write the `.npz`
    snapshot the plan service loads (keys n, src, dst, w, name)."""
    g = jaxpr_to_graph(step_jaxpr(config), name=config["name"])
    with open(path, "wb") as f:
        np.savez_compressed(f, **g)


# ---------------------------------------------------------------------- #
# the architecture
# ---------------------------------------------------------------------- #
def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, cos, sin):
    """Rotate-half RoPE over the last axis."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(x, p, c, cos, sin, causal):
    b, t, _ = x.shape
    nh, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"], c["v_head_dim"])
    eps = c["rms_norm_eps"]
    q = _rms(x @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]
    q = q.reshape(b, t, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], cos[:, None], sin[:, None])
    kv = x @ p["wkv_a"]
    c_kv, k_pe = kv[..., :c["kv_lora_rank"]], kv[..., c["kv_lora_rank"]:]
    k_pe = _rope(k_pe, cos, sin)
    kv = (_rms(c_kv, p["kv_norm"], eps) @ p["wkv_b"]).reshape(b, t, nh,
                                                              dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe,
                           preferred_element_type=jnp.float32))
    scores = jnp.where(causal, scores * (dn + dr) ** -0.5, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, nh * dv)
    return out @ p["wo"]


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _moe(x, p, c, capacity):
    b, t, h = x.shape
    xt = x.reshape(b * t, h)
    e_all, k = c["n_routed_experts"], c["num_experts_per_tok"]
    groups = c["n_group"]
    scores = jax.nn.sigmoid(xt.astype(jnp.float32) @ p["router"])
    choice = scores + p["router_bias"]
    best2 = lax.top_k(choice.reshape(b * t, groups, e_all // groups), 2)[0]
    _, top_groups = lax.top_k(best2.sum(-1), c["topk_group"])
    keep = jax.nn.one_hot(top_groups, groups, dtype=jnp.float32).sum(-2)
    keep = jnp.repeat(keep, e_all // groups, axis=-1)
    _, idx = lax.top_k(jnp.where(keep > 0, choice, 0.0), k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if c["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * c["routed_scaling_factor"]
    gate = (jax.nn.one_hot(idx, e_all, dtype=jnp.float32)
            * gates[..., None]).sum(-2)
    out = _swiglu(xt, p["shared_w1"], p["shared_w3"], p["shared_w2"])
    for e, expert in enumerate(p["experts"]):
        g_e, tok = lax.top_k(gate[:, e], capacity)
        y = _swiglu(xt[tok], expert["w1"], expert["w3"], expert["w2"])
        out = out.at[tok].add(y * g_e[:, None].astype(y.dtype))
    return out.reshape(b, t, h)


def _block(x, p, c, cos, sin, causal, capacity):
    eps = c["rms_norm_eps"]
    x = x + _mla(_rms(x, p["attn_norm"], eps), p["attn"], c, cos, sin,
                 causal)
    y = _rms(x, p["ffn_norm"], eps)
    if "router" in p:
        return x + _moe(y, p, c, capacity)
    return x + _swiglu(y, p["w1"], p["w3"], p["w2"])


def _xent(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def _loss(params, tokens, c):
    s = c["step"]
    t, eps = s["seq_len"], c["rms_norm_eps"]
    capacity = s["expert_capacity"]
    dr = c["qk_rope_head_dim"]
    pos = jnp.arange(t, dtype=jnp.float32)
    inv = c["rope_theta"] ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = pos[:, None] * inv[None, :]
    dtype = jnp.dtype(s["param_dtype"])
    cos, sin = jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        return _block(x, p, c, cos, sin, causal, capacity), None

    x = params["embed"][tokens[:, :t]]
    x = lax.scan(layer, x, params["dense"])[0]
    x = lax.scan(layer, x, params["moe"])[0]
    head = params["head"]

    def logits(h, norm):
        return jnp.einsum("bth,hv->btv", _rms(h, norm, eps), head,
                          preferred_element_type=jnp.float32)

    loss = _xent(logits(x, params["final_norm"]), tokens[:, 1:t + 1])
    for depth, mtp in enumerate(params["mtp"], start=1):
        e = params["embed"][tokens[:, depth:t + depth]]
        x = jnp.concatenate([_rms(x, mtp["h_norm"], eps),
                             _rms(e, mtp["e_norm"], eps)], -1) @ mtp["proj"]
        x = _block(x, mtp["block"], c, cos, sin, causal, capacity)
        loss = loss + s["mtp_loss_weight"] / len(params["mtp"]) * _xent(
            logits(x, mtp["final_norm"]), tokens[:, depth + 1:t + depth + 1])
    return loss


def param_shapes(c: dict) -> dict:
    """ShapeDtypeStructs of every parameter (the router and its bias in
    float32, the rest in the step's parameter dtype)."""
    dtype = jnp.dtype(c["step"]["param_dtype"])
    h, v = c["hidden_size"], c["vocab_size"]
    nh, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"], c["v_head_dim"])
    e_all, fe = c["n_routed_experts"], c["moe_intermediate_size"]
    fs = fe * c["n_shared_experts"]

    def sds(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt)

    def attn():
        return {"wq_a": sds(h, c["q_lora_rank"]),
                "q_norm": sds(c["q_lora_rank"]),
                "wq_b": sds(c["q_lora_rank"], nh * (dn + dr)),
                "wkv_a": sds(h, c["kv_lora_rank"] + dr),
                "kv_norm": sds(c["kv_lora_rank"]),
                "wkv_b": sds(c["kv_lora_rank"], nh * (dn + dv)),
                "wo": sds(nh * dv, h)}

    def dense():
        f = c["intermediate_size"]
        return {"attn_norm": sds(h), "attn": attn(), "ffn_norm": sds(h),
                "w1": sds(h, f), "w3": sds(h, f), "w2": sds(f, h)}

    def moe():
        return {"attn_norm": sds(h), "attn": attn(), "ffn_norm": sds(h),
                "router": sds(h, e_all, dt=jnp.float32),
                "router_bias": sds(e_all, dt=jnp.float32),
                "shared_w1": sds(h, fs), "shared_w3": sds(h, fs),
                "shared_w2": sds(fs, h),
                "experts": [{"w1": sds(h, fe), "w3": sds(h, fe),
                             "w2": sds(fe, h)} for _ in range(e_all)]}

    def stacked(tree, n):
        return jax.tree.map(lambda a: sds(n, *a.shape, dt=a.dtype), tree)

    n_dense = c["first_k_dense_replace"]
    return {"embed": sds(v, h),
            "dense": stacked(dense(), n_dense),
            "moe": stacked(moe(), c["num_hidden_layers"] - n_dense),
            "final_norm": sds(h), "head": sds(h, v),
            "mtp": [{"h_norm": sds(h), "e_norm": sds(h),
                     "proj": sds(2 * h, h), "block": moe(),
                     "final_norm": sds(h)}
                    for _ in range(c["num_nextn_predict_layers"])]}


def step_jaxpr(c: dict):
    """The closed jaxpr of one training step's gradient: forward, both
    losses and backward, over one (batch, seq_len + depth + 1) token
    window."""
    if c["tie_word_embeddings"] or c["scoring_func"] != "sigmoid":
        raise ValueError("the step is written for untied embeddings and "
                         "sigmoid routing")
    s = c["step"]
    tokens = jax.ShapeDtypeStruct(
        (s["batch"], s["seq_len"] + c["num_nextn_predict_layers"] + 1),
        jnp.int32)
    return jax.make_jaxpr(jax.grad(lambda p, x: _loss(p, x, c)))(
        param_shapes(c), tokens)


# ---------------------------------------------------------------------- #
# jaxpr -> graph: a copy of the program's core/jaxpr_graph.py, uncapped
# ---------------------------------------------------------------------- #
_CALL_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr")


def _aval_bytes(aval) -> float:
    try:
        size = int(np.prod(aval.shape)) if aval.shape else 1
        itemsize = np.dtype(aval.dtype).itemsize
        return float(size * itemsize)
    except Exception:
        return 8.0


def jaxpr_to_graph(closed_jaxpr, name: str = "jaxpr") -> dict:
    """The graph of a closed jaxpr as the `.npz` snapshot's arrays: one
    vertex per primitive (call-like primitives inlined, every scan
    unrolled over its whole length), one edge per def-use, weighted by
    the bytes of the value (at least 1)."""
    src: list = []
    dst: list = []
    w: list = []
    n = 0

    def new_node() -> int:
        nonlocal n
        n += 1
        return n - 1

    def resolve(var, env: dict) -> int:
        if isinstance(var, jcore.Literal):
            return new_node()
        if var not in env:
            env[var] = new_node()
        return env[var]

    def walk(jaxpr, env: dict) -> None:
        for eqn in jaxpr.eqns:
            inner = None
            for pname in _CALL_PARAMS:
                if pname in eqn.params:
                    inner = eqn.params[pname]
                    break
            if inner is not None and eqn.primitive.name in (
                    "pjit", "custom_jvp_call", "custom_vjp_call",
                    "remat", "checkpoint", "closed_call"):
                ij = inner.jaxpr if hasattr(inner, "jaxpr") else inner
                sub_env = {}
                for var, outer in zip(ij.invars, eqn.invars):
                    sub_env[var] = resolve(outer, env)
                for var, _ in zip(ij.constvars, getattr(inner, "consts", [])):
                    sub_env[var] = new_node()
                walk(ij, sub_env)
                for outer_out, inner_out in zip(eqn.outvars, ij.outvars):
                    env[outer_out] = resolve(inner_out, sub_env)
                continue
            if inner is not None and eqn.primitive.name == "scan":
                ij = inner.jaxpr if hasattr(inner, "jaxpr") else inner
                length = int(eqn.params.get("length", 1))
                n_carry = eqn.params.get("num_carry", 0)
                n_consts = eqn.params.get("num_consts", 0)
                carry = [resolve(v, env)
                         for v in eqn.invars[n_consts:n_consts + n_carry]]
                consts = [resolve(v, env) for v in eqn.invars[:n_consts]]
                xs = [resolve(v, env)
                      for v in eqn.invars[n_consts + n_carry:]]
                for _ in range(length):
                    sub_env = dict(zip(ij.invars, consts + carry + xs))
                    for var in ij.constvars:
                        sub_env[var] = new_node()
                    walk(ij, sub_env)
                    carry = [resolve(v, sub_env) for v in ij.outvars][:n_carry]
                for outer_out, nid in zip(eqn.outvars[:n_carry], carry):
                    env[outer_out] = nid
                for outer_out in eqn.outvars[n_carry:]:
                    env[outer_out] = new_node()
                continue
            nid = new_node()
            for iv in eqn.invars:
                src.append(resolve(iv, env))
                dst.append(nid)
                w.append(max(_aval_bytes(iv.aval), 1.0))
            for ov in eqn.outvars:
                env[ov] = nid

    top = closed_jaxpr.jaxpr
    env: dict = {}
    for var in list(top.invars) + list(top.constvars):
        env[var] = new_node()
    walk(top, env)
    return {"n": n, "src": np.asarray(src, np.int32),
            "dst": np.asarray(dst, np.int32),
            "w": np.asarray(w, np.float64), "name": name}
