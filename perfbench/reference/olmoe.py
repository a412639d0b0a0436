"""OLMoE's forward pass, loss and gradients, plainly (Muennighoff et al.
2024, "OLMoE: Open Mixture-of-Experts Language Models", and the published
``modeling_olmoe.py``), over the parameter tree the program's ``GPT`` holds:
``wte``, ``lm_head``, ``ln_f`` and ``h/block`` with a leading layer axis
(``attn/c_attn`` holds q, k and v side by side, ``mlp/gate`` is the router,
``mlp/experts/{wg, wi, wo}`` are gate, up and down of every expert).

Float32 throughout; callers wrap it in
``jax.default_matmul_precision("highest")``. A Python loop over the layers
and, inside each, over the experts with a dense mask: every expert is
applied to every token and what the router did not choose is multiplied by
zero. No sort, no grouped matmul, no scan, no recomputation, no kernel.

The block, as published: pre-RMSNorm; q, k, v without bias; an RMSNorm over
the whole width of q and of k (not per head) before rotary (rotate-half,
full head); causal softmax attention; output projection; residual;
RMSNorm; the router's softmax over all experts in float32, the k largest
probabilities taken as they are (``norm_topk_prob`` false: they do not sum
to one); SwiGLU experts ``down(silu(gate x) * up x)``; residual. Untied
head, no position table.

Departures from the published code, each of them the program's too:

* the load-balancing loss is the paper's per layer, ``E * sum_i f_i P_i``
  with ``f_i`` the share of the tokens * k routed pairs that went to expert
  i and ``P_i`` the mean router probability of expert i, averaged over the
  layers (as the training code the paper used computes it); the published
  inference code's helper pools the layers and scales by k instead;
* the router z-loss is the mean over tokens of ``logsumexp(logits) ** 2``,
  averaged over the layers; neither coefficient is a key of ``config.json``
  (0.01 and 0.001 are the paper's);
* the cross entropy is the mean over the positions that have a next token
  (the last position of each row has none).
"""
import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """Rotate-half rotary embedding over the whole head of [B, T, H, D]."""
    t, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def attention(x, p, n_head, eps, theta):
    b, t, c = x.shape
    d = c // n_head
    qkv = x @ p["c_attn"]["kernel"]
    q, k, v = (qkv[..., i * c:(i + 1) * c] for i in range(3))
    q = rms_norm(q, p["q_norm"]["scale"], eps).reshape(b, t, n_head, d)
    k = rms_norm(k, p["k_norm"]["scale"], eps).reshape(b, t, n_head, d)
    q, k = rotary(q, theta), rotary(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v.reshape(b, t, n_head, d))
    return a.reshape(b, t, c) @ p["c_proj"]["kernel"]


def route(x, kernel, top_k, renormalize):
    """``(weights [N, E], chosen [N, E] bool, load balancing, z)`` of the
    tokens ``x`` [N, C]: the chosen experts' probabilities, zero
    elsewhere."""
    logits = x @ kernel
    probs = jax.nn.softmax(logits, axis=-1)
    n, e = probs.shape
    _, idx = jax.lax.top_k(probs, top_k)
    chosen = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None], idx].set(True)
    weights = jnp.where(chosen, probs, 0.0)
    if renormalize:
        weights = weights / weights.sum(-1, keepdims=True)
    share = chosen.sum(0) / (n * top_k)
    balance = e * jnp.sum(share * probs.mean(0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return weights, chosen, balance, z


def experts(x, weights, p):
    """Every expert on every token, weighted; [N, C] -> [N, C]."""
    y = jnp.zeros_like(x)
    for e in range(p["wi"].shape[0]):
        h = jax.nn.silu(x @ p["wg"][e]) * (x @ p["wi"][e])
        y = y + weights[:, e:e + 1] * (h @ p["wo"][e])
    return y


def embed(params, ids):
    return params["wte"]["embedding"][ids]


def block(x, p, *, n_head, top_k, eps=1e-5, theta=10000.0,
          renormalize=False):
    """One layer on [B, T, C] with its parameters ``p`` (no layer axis):
    ``(x, chosen [B*T, E] bool, load balancing, z)``."""
    b, t, _ = x.shape
    x = x + attention(rms_norm(x, p["ln_1"]["scale"], eps), p["attn"],
                      n_head, eps, theta)
    h = rms_norm(x, p["ln_2"]["scale"], eps).reshape(b * t, -1)
    w, chosen, balance, z = route(h, p["mlp"]["gate"]["kernel"], top_k,
                                  renormalize)
    x = x + experts(h, w, p["mlp"]["experts"]).reshape(x.shape)
    return x, chosen, balance, z


def head_loss(x, ln_f, lm_head, ids, eps=1e-5):
    """Mean next-token cross entropy of the final hidden states (labels
    are the inputs, shifted), and the logits."""
    logits = rms_norm(x, ln_f["scale"], eps) @ lm_head
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1)), logits


def forward(params, ids, *, eps=1e-5, **kw):
    """``(cross entropy, logits [B, T, V], chosen [L, B*T, E] bool,
    balance [L], z [L])`` of the float32 parameter tree ``params`` on the
    token ids [B, T]."""
    x = embed(params, ids)
    layers = params["h"]["block"]
    chosen, balance, z = [], [], []
    for i in range(layers["ln_1"]["scale"].shape[0]):
        x, c, lb, lz = block(x, jax.tree.map(lambda a, i=i: a[i], layers),
                             eps=eps, **kw)
        chosen.append(c), balance.append(lb), z.append(lz)
    ce, logits = head_loss(x, params["ln_f"], params["lm_head"], ids, eps)
    return ce, logits, jnp.stack(chosen), jnp.stack(balance), jnp.stack(z)


def loss(params, ids, *, balance_coef, z_coef, **kw):
    """The training loss: cross entropy plus the two auxiliary losses
    averaged over the layers; ``(loss, (cross entropy, chosen))``."""
    ce, _, chosen, balance, z = forward(params, ids, **kw)
    total = ce + balance_coef * balance.mean() + z_coef * z.mean()
    return total, (ce, chosen)


def loss_and_grads(params, ids, **kw):
    """``(loss, (cross entropy, chosen), grads)`` in float32 at ``highest``
    matmul precision; ``params`` may hold any float dtype and is cast."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(
            params, ids, **kw)
    return value, aux, grads
