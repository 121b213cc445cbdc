"""Plain float32 reference of Qwen1.5-0.5B (the Qwen2 decoder), written from
the published description and config: token embedding; per layer RMSNorm,
causal multi-head attention with q/k/v biases and rotary position
embedding (rotate-half form), residual, RMSNorm, SwiGLU MLP, residual;
final RMSNorm; logits against the tied embedding; next-token cross-entropy.

It imports nothing of the program under test. ``to_program`` lays its
parameters out as the program's parameter tree, so that the benchmark can
hand the same weights to both.
"""
import math

import jax
import jax.numpy as jnp


def sizes(m):
    return (int(m["vocab_size"]), int(m["hidden_size"]),
            int(m["num_hidden_layers"]), int(m["num_attention_heads"]),
            int(m["num_key_value_heads"]), int(m["intermediate_size"]))


def init(key, m):
    """Weights from one key: normal(0, fan_in^-1/2) matrices, N(0, 0.02)
    biases, unit norm scales."""
    V, d, L, H, KV, F = sizes(m)
    hd = d // H
    ks = iter(jax.random.split(key, 16))

    def nrm(shape, std):
        return std * jax.random.normal(next(ks), shape, jnp.float32)

    return {
        "embed": nrm((V, d), d ** -0.5),
        "final_norm": jnp.ones((d,), jnp.float32),
        "ln1": jnp.ones((L, d), jnp.float32),
        "ln2": jnp.ones((L, d), jnp.float32),
        "wq": nrm((L, d, H * hd), d ** -0.5), "bq": nrm((L, H * hd), 0.02),
        "wk": nrm((L, d, KV * hd), d ** -0.5), "bk": nrm((L, KV * hd), 0.02),
        "wv": nrm((L, d, KV * hd), d ** -0.5), "bv": nrm((L, KV * hd), 0.02),
        "wo": nrm((L, H * hd, d), (H * hd) ** -0.5),
        "w_gate": nrm((L, d, F), d ** -0.5),
        "w_up": nrm((L, d, F), d ** -0.5),
        "w_down": nrm((L, F, d), F ** -0.5),
    }


def to_program(p):
    """The program's layout: layers stacked under ``stack.b0``."""
    return {
        "embed": {"embedding": p["embed"]},
        "final_norm": {"scale": p["final_norm"]},
        "stack": {"b0": {
            "ln1": {"scale": p["ln1"]},
            "attn": {"wq": {"kernel": p["wq"], "bias": p["bq"]},
                     "wk": {"kernel": p["wk"], "bias": p["bk"]},
                     "wv": {"kernel": p["wv"], "bias": p["bv"]},
                     "wo": {"kernel": p["wo"]}},
            "ln2": {"scale": p["ln2"]},
            "mlp": {"gate": {"kernel": p["w_gate"]},
                    "up": {"kernel": p["w_up"]},
                    "down": {"kernel": p["w_down"]}}}},
    }


def _rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x (B, S, H, hd): rotate-half RoPE at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    half = hd // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def loss(p, x, y, m):
    """Mean next-token cross-entropy of token rows ``x`` (B, S): position t
    predicts ``x[:, t + 1]`` (``y`` is not read)."""
    del y
    V, d, L, H, KV, F = sizes(m)
    hd = d // H
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    B, S = x.shape
    h = p["embed"][x]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(h, w):
        a = _rmsnorm(h, w["ln1"], eps)
        q = (a @ w["wq"] + w["bq"]).reshape(B, S, H, hd)
        k = (a @ w["wk"] + w["bk"]).reshape(B, S, KV, hd)
        v = (a @ w["wv"] + w["bv"]).reshape(B, S, KV, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        s = jnp.where(causal, s / math.sqrt(hd), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(B, S, H * hd)
        h = h + o @ w["wo"]
        a = _rmsnorm(h, w["ln2"], eps)
        h = h + (jax.nn.silu(a @ w["w_gate"]) * (a @ w["w_up"])) @ w["w_down"]
        return h, None

    layers = {k: p[k] for k in ("ln1", "ln2", "wq", "bq", "wk", "bk", "wv",
                                "bv", "wo", "w_gate", "w_up", "w_down")}
    h, _ = jax.lax.scan(layer, h, layers)
    h = _rmsnorm(h, p["final_norm"], eps)
    logits = (h[:, :-1] @ p["embed"].T).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, x[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def param_count(m):
    V, d, L, H, KV, F = sizes(m)
    hd = d // H
    per_layer = (2 * d + d * H * hd + H * hd + 2 * (d * KV * hd + KV * hd)
                 + H * hd * d + 3 * d * F)
    return V * d + d + L * per_layer


def flops_per_sample(m, g):
    """Training operations of one sample (a row of ``seq`` tokens): 6 per
    parameter per token for the matrices the tokens pass through (the tied
    embedding as the output head; the lookup costs none), plus attention's
    scores and weighted sum, 12 * layers * d_model * seq per token. What is
    recomputed is not counted."""
    V, d, L, H, KV, F = sizes(m)
    S = int(g["seq"])
    hd = d // H
    matrices = V * d + L * (d * H * hd + 2 * d * KV * hd + H * hd * d
                            + 3 * d * F)
    return S * (6 * matrices + 12 * L * d * S)
