"""GPT-2's forward pass, plainly (Radford et al. 2019; pre-LN blocks,
learned positions, tanh GELU, tied head), over the parameter tree the
program's ``GPT`` holds: ``wte``, ``wpe``, ``ln_f`` and ``h/block`` with a
leading layer axis. Float32 throughout, matmuls at ``highest`` precision.
"""
import functools

import jax
import jax.numpy as jnp


def layer_norm(x, p, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, n_head):
    t, c = x.shape
    d = c // n_head
    qkv = dense(layer_norm(x, p["ln_1"]), p["attn"]["c_attn"])
    q, k, v = (qkv[:, i * c:(i + 1) * c].reshape(t, n_head, d)
               for i in range(3))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, c)
    x = x + dense(a, p["attn"]["c_proj"])
    h = gelu_tanh(dense(layer_norm(x, p["ln_2"]), p["mlp"]["c_fc"]))
    return x + dense(h, p["mlp"]["c_proj"])


def hidden(params, ids, n_head):
    """[T, C] float32 final hidden states (after ``ln_f``) of one unpadded
    or right-padded sequence (causal attention keeps padding on the right
    out of every earlier position)."""
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    t = ids.shape[0]
    wte = params["wte"]["embedding"].astype(jnp.float32)
    x = wte[ids] + params["wpe"]["embedding"].astype(jnp.float32)[:t]

    def body(x, layer):
        return block(x, f32(layer), n_head), None

    x, _ = jax.lax.scan(body, x, params["h"]["block"])
    return layer_norm(x, f32(params["ln_f"]))


def logits(params, ids, n_head):
    """[T, vocab] float32 logits (the head is tied to ``wte``)."""
    return hidden(params, ids, n_head) \
        @ params["wte"]["embedding"].astype(jnp.float32).T


def make_last_logits(n_head, n_positions):
    """A jitted ``(params, ids[n_positions], n) -> logits[vocab]`` at
    position ``n - 1``; one shape, so one compilation."""

    @jax.jit
    def last(params, ids, n):
        with jax.default_matmul_precision("highest"):
            x = hidden(params, ids, n_head)[n - 1]
            return x @ params["wte"]["embedding"].astype(jnp.float32).T

    return last
