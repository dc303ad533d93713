"""Plain Qwen2 reference, written from the published description.

Decoder-only transformer: token embedding; per layer RMSNorm -> grouped-query
attention with biased q/k/v projections and rotary positions (rotate-half,
theta from the config) -> residual -> RMSNorm -> SwiGLU MLP -> residual;
final RMSNorm; an untied output head. Everything is computed in float32 at
``highest`` matmul precision. Nothing of the system under test is imported.

The weights are drawn from the seed by ``init_params``: normal draws scaled
by 1/sqrt(fan-in), zero biases, unit norm scales, stored in the dtype the
configuration states. Keys are split in the order the system's own
initializer splits them, so the same seed gives the same weights; the
reference makes its own copy and takes nothing the system made.

The control is the same computation with both operands of every matmul
rounded to the nearest precision below the configuration's dtype
(``control_quant``): float8 e4m3 under a per-tensor scale for bfloat16,
bfloat16 for float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def dims(cfg):
    """The sizes the reference needs, from a configuration file's dict."""
    return dict(
        L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
        H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], ff=cfg["intermediate_size"], V=cfg["vocab_size"],
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.dtype(cfg["torch_dtype"]),
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _dense(key, d_in, d_out, dtype, bias):
    w_key = jax.random.split(key, 2)[0]
    p = {"w": _normal(w_key, (d_in, d_out), d_in, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def _layer(key, m):
    d, dt = m["d"], m["dtype"]
    ks = jax.random.split(key, 6)
    qk = jax.random.split(ks[0], 4)
    fk = jax.random.split(ks[2], 3)
    return {
        "norm1": {"scale": jnp.ones((d,), dt)},
        "mixer": {
            "q": _dense(qk[0], d, m["H"] * m["hd"], dt, True),
            "k": _dense(qk[1], d, m["KV"] * m["hd"], dt, True),
            "v": _dense(qk[2], d, m["KV"] * m["hd"], dt, True),
            "o": _dense(qk[3], m["H"] * m["hd"], d, dt, False),
        },
        "norm2": {"scale": jnp.ones((d,), dt)},
        "ffn": {
            "wg": _normal(fk[0], (d, m["ff"]), d, dt),
            "wu": _normal(fk[1], (d, m["ff"]), d, dt),
            "wd": _normal(fk[2], (m["ff"], d), m["ff"], dt),
        },
    }


def init_params(cfg, key):
    """Weights from ``key`` in the configuration's dtype (call under jit)."""
    m = dims(cfg)
    ks = jax.random.split(key, 4)
    seg = jax.random.fold_in(ks[1], 0)
    layers = jax.vmap(
        lambda r: {"l0": _layer(jax.random.fold_in(jax.random.fold_in(seg, r), 0), m)}
    )(jnp.arange(m["L"]))
    return {
        "embed": {"table": (jax.random.normal(ks[0], (m["V"], m["d"]))).astype(m["dtype"])},
        "segments": (layers,),
        "final_norm": {"scale": jnp.ones((m["d"],), m["dtype"])},
        "lm_head": {"w": _normal(ks[2], (m["d"], m["V"]), m["d"], m["dtype"])},
    }


def make_params(cfg, seed):
    """``init_params`` from ``PRNGKey(seed)`` as one jitted call on the
    default device (the key is made outside the jit: a seed may exceed 32
    bits)."""
    return jax.jit(init_params, static_argnums=(0,))(_Frozen(cfg), jax.random.PRNGKey(seed))


class _Frozen(dict):
    """A hashable configuration dict, so it can be a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def control_quant(cfg):
    """The precision the control computes in: the nearest below the
    configuration's."""
    return {"bfloat16": "fp8", "float32": "bf16"}[cfg["torch_dtype"]]


def _cast(x, quant):
    if quant == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / s).astype(FP8).astype(jnp.float32) * s
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, quant):
    return _cast(x, quant)


def _round_fwd(x, quant):
    return _cast(x, quant), None


def _round_bwd(quant, _, ct):
    return (_cast(ct, quant),)


_round.defvjp(_round_fwd, _round_bwd)


def _q(x, quant):
    """Round to ``quant`` (float8 e4m3 under a per-tensor scale, or
    bfloat16) and back to float32; the gradient flowing back through it is
    rounded the same way under its own scale, as low-precision training
    does. None leaves x as it is."""
    return x if quant is None else _round(x, quant)


def _mm(x, w, quant):
    x, w = _q(x.astype(jnp.float32), quant), _q(w.astype(jnp.float32), quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [s, n, hd]; rotate-half rotary embedding at positions ``pos`` [s]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, lp, m, quant):
    """One decoder layer on one sequence x [s, d] (float32)."""
    s = x.shape[0]
    a = lp["mixer"]
    h = _rmsnorm(x, lp["norm1"]["scale"], m["eps"])
    q = (_mm(h, a["q"]["w"], quant) + a["q"]["b"].astype(jnp.float32)).reshape(s, m["H"], m["hd"])
    k = (_mm(h, a["k"]["w"], quant) + a["k"]["b"].astype(jnp.float32)).reshape(s, m["KV"], m["hd"])
    v = (_mm(h, a["v"]["w"], quant) + a["v"]["b"].astype(jnp.float32)).reshape(s, m["KV"], m["hd"])
    pos = jnp.arange(s)
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    g = m["H"] // m["KV"]
    qg = q.reshape(s, m["KV"], g, m["hd"])
    qg, k, v = _q(qg, quant), _q(k, quant), _q(v, quant)
    sc = jnp.einsum("qkgd,ckd->kgqc", qg, k, precision=HIGHEST) / math.sqrt(m["hd"])
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    p = _q(p, quant)
    o = jnp.einsum("kgqc,ckd->qkgd", p, v, precision=HIGHEST).reshape(s, m["H"] * m["hd"])
    x = x + _mm(o, a["o"]["w"], quant)
    f = lp["ffn"]
    h2 = _rmsnorm(x, lp["norm2"]["scale"], m["eps"])
    y = jax.nn.silu(_mm(h2, f["wg"], quant)) * _mm(h2, f["wu"], quant)
    return x + _mm(y, f["wd"], quant)


def hidden(params, tokens, m, quant=None, remat=False):
    """Final-normed hidden states [s, d] of one sequence of token ids."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    block = functools.partial(_block, m=m, quant=quant)
    if remat:
        block = jax.checkpoint(block)

    def body(x, lp):
        return block(x, lp["l0"]), None

    x, _ = jax.lax.scan(body, x, params["segments"][0])
    return _rmsnorm(x, params["final_norm"]["scale"], m["eps"])


@functools.partial(jax.jit, static_argnames=("m_items", "quant"))
def _logits_at(params, tokens, at, m_items, quant=None):
    m = dict(m_items)
    h = hidden(params, tokens, m, quant)
    return _mm(h[at], params["lm_head"]["w"], quant)


def logits_at(params, cfg, tokens, at, quant=None):
    """Logits [len(at), V] at positions ``at`` of one sequence. The sequence
    is right-padded to a power of two (causality keeps the pads out of every
    earlier position) so few shapes compile."""
    m = dims(cfg)
    n, k = len(tokens), len(at)
    S, K = _pow2(n, 256), _pow2(k, 16)
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    idx = np.zeros(K, np.int32)
    idx[:k] = at
    out = _logits_at(params, jnp.asarray(toks), jnp.asarray(idx),
                     tuple(sorted(m.items(), key=lambda kv: kv[0])), quant)
    return out[:k]


def _pow2(n, floor):
    return max(floor, 1 << (int(n) - 1).bit_length())


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def row_loss_sum(params, tokens, labels, m, quant=None):
    """Summed next-token cross entropy of one row."""
    h = hidden(params, tokens, m, quant, remat=True)
    logits = _mm(h, params["lm_head"]["w"], quant)
    lse = jax.nn.logsumexp(logits, -1)
    return jnp.sum(lse - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0])


def batch_grad(params, tokens, labels, m, quant=None):
    """(mean loss, mean gradient) over all tokens of a batch, one row at a
    time so the activations of one row are live at once."""
    grad_row = jax.value_and_grad(row_loss_sum)
    zeros = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, row):
        tot, g = carry
        l, gr = grad_row(params, row[0], row[1], m, quant)
        return (tot + l, jax.tree_util.tree_map(lambda a, b: a + b.astype(jnp.float32), g, gr)), None

    (tot, g), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zeros), (tokens, labels))
    n = tokens.shape[0] * tokens.shape[1]
    return tot / n, jax.tree_util.tree_map(lambda x: x / n, g)


def adamw_lr(opt, step):
    """Linear warm-up to ``lr``, then cosine decay to ``min_lr_frac``."""
    warm = min(1.0, step / max(opt["warmup_steps"], 1))
    t = min(max((step - opt["warmup_steps"]) / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    cos = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (1 + math.cos(math.pi * t))
    return opt["lr"] * warm * cos


@functools.partial(jax.jit, static_argnames=("m_items", "quant"), donate_argnums=(0,))
def _train_step(state, tokens, labels, lr, b1c, b2c, hyper, m_items, quant=None):
    """One AdamW step (decoupled weight decay, global-norm clipping) on
    float32 master weights; the forward uses them rounded to the stored
    dtype, as mixed-precision training with float32 masters does."""
    m = dict(m_items)
    b1, b2, eps, wd, clip = hyper
    master = state["master"]
    params = jax.tree_util.tree_map(lambda x: x.astype(m["dtype"]), master)
    loss, g = batch_grad(params, tokens, labels, m, quant)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
    g = jax.tree_util.tree_map(lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9)), g)
    mom = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, state["m"], g)
    vel = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, state["v"], g)
    master = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * ((a / b1c) / (jnp.sqrt(b / b2c) + eps) + wd * p),
        master, mom, vel)
    return {"master": master, "m": mom, "v": vel}, loss


def leaf_norms(tree):
    """{leaf path: float32 norm} of a pytree (a jitted reduction)."""
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    norms = jax.jit(lambda t: [jnp.linalg.norm(x.astype(jnp.float32)) for x in jax.tree_util.tree_leaves(t)])(tree)
    return dict(zip(paths, (float(n) for n in norms)))


def train_readings(cfg, seed, batches, opt, quant=None, rows=None):
    """Run the reference's first len(batches) AdamW steps from the seed's
    weights: {"loss": [per step], "m1": {leaf: norm of the first moment after
    step 1}, "m1_host": that first moment on the host, "update": {leaf: norm
    of the master's change after the last step}}. ``rows`` keeps only the first rows of each batch (a planted
    fault: half the batch left out)."""
    m = dims(cfg)
    m_items = tuple(sorted(m.items(), key=lambda kv: kv[0]))
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)
    master = f32(make_params(cfg, seed))
    state = {
        "master": master,
        "m": jax.tree_util.tree_map(jnp.zeros_like, master),
        "v": jax.tree_util.tree_map(jnp.zeros_like, master),
    }
    del master
    hyper = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"], opt["grad_clip"])
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for i, (toks, labels) in enumerate(batches, start=1):
            if rows:
                toks, labels = toks[:rows], labels[:rows]
            state, loss = _train_step(
                state, jnp.asarray(toks), jnp.asarray(labels),
                adamw_lr(opt, i), 1 - opt["b1"] ** i, 1 - opt["b2"] ** i,
                hyper, m_items, quant)
            out["loss"].append(float(loss))
            if i == 1:
                out["m1"] = leaf_norms(state["m"])
                out["m1_host"] = jax.device_get(state["m"])
        master = state["master"]
        del state
        out["update"] = leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b.astype(jnp.float32), master, make_params(cfg, seed)))
    return out
