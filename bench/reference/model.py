"""Plain float32 forward pass of a Llama-family dense decoder (Yi-6B,
Mistral-7B): RMSNorm, rotary positions (rotate-half convention), causal
grouped-query attention, SwiGLU MLP, untied output head.

Parameters are a nested dict in the benchmark's layout (``param_shapes``);
per-layer leaves under ``blocks`` are stacked on a leading layer axis.
Every contraction goes through :func:`contract`, which runs at float32
``HIGHEST`` precision, or - for the lower-precision control - on operands
rounded to float8 (e4m3, one scale per tensor; the cotangents of the
backward pass are rounded the same way).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0       # largest finite float8_e4m3fn value


def dims(run: dict) -> dict:
    """Sizes of the decoder from a configuration file's ``run`` section
    (Hugging Face key names)."""
    d = int(run["hidden_size"])
    H = int(run["num_attention_heads"])
    return {"d": d, "H": H, "K": int(run["num_key_value_heads"]),
            "hd": int(run.get("head_dim") or d // H),
            "f": int(run["intermediate_size"]),
            "V": int(run["vocab_size"]), "L": int(run["num_hidden_layers"]),
            "theta": float(run["rope_theta"]),
            "eps": float(run["rms_norm_eps"])}


def param_shapes(dm: dict) -> dict:
    """Shape of every parameter, in the benchmark's layout."""
    d, H, K, hd, f, V, L = (dm[k] for k in ("d", "H", "K", "hd", "f", "V",
                                             "L"))
    return {
        "embed": (V, d),
        "final_norm": {"w": (d,)},
        "unembed": (d, V),
        "blocks": {
            "ln1": {"w": (L, d)},
            "attn": {"q": (L, d, H * hd), "k": (L, d, K * hd),
                     "v": (L, d, K * hd), "o": (L, H * hd, d)},
            "ln2": {"w": (L, d)},
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        },
    }


# ---------------------------------------------------------------------------
# precision of the contractions
# ---------------------------------------------------------------------------

def _fp8_round(x):
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def fp8(x):
    """Round to float8 e4m3 with one scale per tensor; the cotangent is
    rounded the same way on the way back."""
    return _fp8_round(x)


def _fp8_fwd(x):
    return _fp8_round(x), None


def _fp8_bwd(_, ct):
    return (_fp8_round(ct),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def contract(spec, a, b, precision="float32"):
    """``einsum`` of two operands, summed in float32."""
    if precision == "float8":
        a, b = fp8(a), fp8(b)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


@functools.lru_cache(maxsize=16)
def rope_tables(S: int, hd: int, theta: float):
    """cos/sin of the rotary angles for positions 0..S-1, worked out in
    float64 on the host."""
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.outer(np.arange(S, dtype=np.float64), inv)
    return (np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32))


def rope(x, cos, sin):
    """x: (B, S, N, hd); rotate-half convention."""
    half = x.shape[-1] // 2
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def layer(x, p, dm, precision="float32"):
    """One decoder layer. x: (B, S, d) float32; p: this layer's weights."""
    B, S, d = x.shape
    H, K, hd = dm["H"], dm["K"], dm["hd"]
    R = H // K
    cos, sin = rope_tables(S, hd, dm["theta"])
    h = rmsnorm(x, p["ln1"]["w"], dm["eps"])
    q = contract("bsd,dn->bsn", h, p["attn"]["q"], precision)
    k = contract("bsd,dn->bsn", h, p["attn"]["k"], precision)
    v = contract("bsd,dn->bsn", h, p["attn"]["v"], precision)
    q = rope(q.reshape(B, S, H, hd), cos, sin).reshape(B, S, K, R, hd)
    k = rope(k.reshape(B, S, K, hd), cos, sin)
    v = v.reshape(B, S, K, hd)
    scores = contract("bskrd,btkd->bkrst", q, k, precision) / np.sqrt(hd)
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = contract("bkrst,btkd->bskrd", probs, v, precision)
    x = x + contract("bsn,nd->bsd", att.reshape(B, S, H * hd),
                     p["attn"]["o"], precision)
    h = rmsnorm(x, p["ln2"]["w"], dm["eps"])
    gate = contract("bsd,df->bsf", h, p["mlp"]["w_gate"], precision)
    up = contract("bsd,df->bsf", h, p["mlp"]["w_up"], precision)
    return x + contract("bsf,fd->bsd", jax.nn.silu(gate) * up,
                        p["mlp"]["w_down"], precision)


def layer_params(blocks, l):
    return jax.tree.map(lambda a: a[l], blocks)


def head(x, params, dm, precision="float32"):
    x = rmsnorm(x, params["final_norm"]["w"], dm["eps"])
    return contract("bsd,dv->bsv", x, params["unembed"], precision)


def forward(params, tokens, dm, precision="float32", remat=False):
    """Logits (B, S, V) in float32 for int tokens (B, S). ``remat``
    recomputes each layer in the backward pass instead of keeping its
    activations (same values, less memory)."""
    x = params["embed"].astype(jnp.float32)[tokens]
    one = functools.partial(layer, dm=dm, precision=precision)
    if remat:
        one = jax.checkpoint(one)
    for l in range(dm["L"]):
        x = one(x, layer_params(params["blocks"], l))
    return head(x, params, dm, precision)


def mean_loss(params, batch, dm, precision="float32", remat=False):
    """Mean next-token cross entropy over the tokens whose mask is 1."""
    logits = forward(params, batch["tokens"], dm, precision, remat)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["targets"][..., None],
                               axis=-1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.sum(mask)
