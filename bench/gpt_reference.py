"""Plain reference for the `train` driver's GPT configurations.

The forward pass, loss and AdamW update of a pre-LayerNorm decoder with
rotary positions, tied embeddings and a tanh-GELU MLP, written out in
float32 `jax.numpy` with every product at `Precision.HIGHEST`.  It
imports nothing of the program.  It reads weights in the layout
`init_params` below makes them (the benchmark makes the weights; the
program is handed the same tree), layers stacked on a leading axis.

`precision="fp8"` is the control: every matrix product's operands are
rounded to float8 with one scale per tensor (e4m3 forward, e5m2 for the
cotangents backward), the step below the configuration's bfloat16.

A configuration names this module as its `reference`; the functions the
`train` driver calls are listed in its docstring.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import flops

HIGHEST = jax.lax.Precision.HIGHEST


# -- the configuration in the program's terms ------------------------------------


def program_fields(model: dict) -> dict:
    """The program's `ModelConfig` fields for nanoGPT's keys: one key and
    value head per query head, heads of `n_embd / n_head`."""
    return {
        "n_layers": model["n_layer"], "d_model": model["n_embd"],
        "n_heads": model["n_head"], "n_kv_heads": model["n_head"],
        "head_dim": model["n_embd"] // model["n_head"], "d_ff": model["n_inner"],
        "vocab_size": model["vocab_size"], "rope_theta": model["rope_theta"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs of one training token (`flops.gpt_train_flops_per_token`)."""
    return flops.gpt_train_flops_per_token(model, seq)


# -- weights and tokens from the seed ------------------------------------------


def key_for(seed: int):
    """A PRNG key from any whole number (more bits than 32 fold in)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_params(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """GPT-2's initialisation (normal, std 0.02; residual projections
    std 0.02 / sqrt(2 * layers); biases 0; norms 1), made in `dtype`."""
    d, ff, v, nl = model["n_embd"], model["n_inner"], model["vocab_size"], model["n_layer"]
    ks = jax.random.split(key, 7)
    std, res = 0.02, 0.02 / math.sqrt(2 * nl)

    def normal(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    def norm():
        return {"scale": jnp.ones((nl, d), dtype), "bias": jnp.zeros((nl, d), dtype)}

    zeros = lambda *shape: jnp.zeros(shape, dtype)
    return {
        "embed": normal(ks[0], (v, d), std),
        "layers": {
            "attn_norm": norm(),
            "attn": {
                "wq": normal(ks[1], (nl, d, d), std),
                "wk": normal(ks[2], (nl, d, d), std),
                "wv": normal(ks[3], (nl, d, d), std),
                "wo": normal(ks[4], (nl, d, d), res),
                "bq": zeros(nl, d), "bk": zeros(nl, d), "bv": zeros(nl, d),
            },
            "mlp_norm": norm(),
            "mlp": {
                "wi": normal(ks[5], (nl, d, ff), std),
                "bi": zeros(nl, ff),
                "wo": normal(ks[6], (nl, ff, d), res),
                "bo": zeros(nl, d),
            },
        },
        "final_norm": {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
    }


@functools.partial(jax.jit, static_argnames=("model", "dtype"))
def make_params(key, *, model: tuple, dtype: str):
    """`init_params` as one compiled program: the program's state and the
    reference start from the same bits.  `model` is the sorted items of
    the configuration's `model` section."""
    return init_params(key, dict(model), jnp.dtype(dtype))


class Tokens:
    """Seeded token rows, power-law tilted so that a short run can lower
    the loss; row block `cursor` is a pure function of (seed, cursor)."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, int(seed)

    def batch_at(self, cursor: int) -> dict:
        import numpy as np

        rng = np.random.default_rng([self.seed % 2**64, int(cursor)])
        u = rng.random((self.batch, self.seq + 1))
        t = np.minimum((self.vocab * u**3).astype(np.int32), self.vocab - 1)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}


# -- float8 rounding for the control ---------------------------------------------


def _fp8(x, dtype):
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.maximum(amax, 1e-30) / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8(x):
    return _fp8(x, jnp.float8_e4m3fn)


fp8.defvjp(lambda x: (_fp8(x, jnp.float8_e4m3fn), None),
           lambda _, g: (_fp8(g, jnp.float8_e5m2),))


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# -- the model -------------------------------------------------------------------


def layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rope(x, theta):
    """Rotate-half rotary embedding of x [B, T, H, D]."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs      # [T, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def loss_fn(params, tokens, labels, model: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over every position of every row.
    The layers run in a `lax.scan` over their stacked weights, which keeps
    the program small; the arithmetic is that of a loop over layers."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    b, t = tokens.shape
    h, d = model["n_head"], model["n_embd"]
    dh, eps, theta = d // h, model["ln_eps"], model["rope_theta"]
    mm = functools.partial(_mm, precision=precision)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, lp):
        a = layer_norm(x, lp["attn_norm"], eps)
        q = mm("btd,de->bte", a, lp["attn"]["wq"]) + lp["attn"]["bq"]
        k = mm("btd,de->bte", a, lp["attn"]["wk"]) + lp["attn"]["bk"]
        v = mm("btd,de->bte", a, lp["attn"]["wv"]) + lp["attn"]["bv"]
        q = rope(q.reshape(b, t, h, dh), theta)
        k = rope(k.reshape(b, t, h, dh), theta)
        v = v.reshape(b, t, h, dh)
        s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = mm("bhqk,bkhd->bqhd", w, v).reshape(b, t, d)
        x = x + mm("btd,de->bte", o, lp["attn"]["wo"])
        m = layer_norm(x, lp["mlp_norm"], eps)
        m = gelu(mm("btd,df->btf", m, lp["mlp"]["wi"]) + lp["mlp"]["bi"])
        return x + mm("btf,fd->btd", m, lp["mlp"]["wo"]) + lp["mlp"]["bo"], None

    x, _ = jax.lax.scan(layer, p["embed"][tokens], p["layers"])
    x = layer_norm(x, p["final_norm"], eps)
    logits = mm("btd,vd->btv", x, p["embed"])
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return (jax.nn.logsumexp(logits, -1) - gold).mean()


# -- the optimizer ------------------------------------------------------------------


def lr_at(opt: dict, step):
    """Linear warm-up to the peak, then cosine to the floor."""
    step = jnp.asarray(step, jnp.float32)
    warm = opt["peak_lr"] * jnp.minimum(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    span = max(opt["decay_steps"] - opt["warmup_steps"], 1)
    prog = jnp.clip((step - opt["warmup_steps"]) / span, 0.0, 1.0)
    cos = opt["min_lr"] + 0.5 * (opt["peak_lr"] - opt["min_lr"]) * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < opt["warmup_steps"], warm, cos)


def decayed(path) -> bool:
    """Weight decay falls on matrices: the embedding and each layer's
    projection weights, not on biases or norm parameters."""
    name = path[-1].key
    return name == "embed" or name.startswith("w")


@functools.partial(jax.jit, static_argnames=("model", "opt", "rows", "precision"))
def train_step(params, mu, nu, count, tokens, labels, *, model, opt, rows,
               precision="f32"):
    """One step: gradient over the batch in blocks of `rows` rows, clip
    by global norm, AdamW; parameters kept in their own dtype.  Returns
    (params, mu, nu, count, loss, clipped gradient)."""
    model, opt = dict(model), dict(opt)
    n = tokens.shape[0] // rows
    grad_fn = jax.value_and_grad(loss_fn)

    def block(acc, rows_):
        l, g = grad_fn(params, rows_[0], rows_[1], model, precision)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    split = lambda a: a.reshape(n, rows, *a.shape[1:])
    (loss, grads), _ = jax.lax.scan(block, (jnp.zeros(()), zeros),
                                    (split(tokens), split(labels)))
    loss = loss / n
    grads = jax.tree.map(lambda g: g / n, grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12)), grads)
    count = count + 1
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    lr = lr_at(opt, count - 1)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)

    def update(path, p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        pf = p.astype(jnp.float32)
        if decayed(path):
            step = step + opt["weight_decay"] * pf
        return (pf - lr * step).astype(p.dtype)

    params = jax.tree_util.tree_map_with_path(update, params, mu, nu)
    return params, mu, nu, count, loss, grads


def leaf_norms(tree) -> dict:
    """Frobenius norm of every leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in flat
    }
