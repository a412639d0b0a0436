"""SmallThinker's forward pass, loss and gradients, plainly (the published
``config.json`` of SmallThinker-21BA3B-Instruct, its modeling code and the
llama.cpp graph), over the parameter tree the program's ``GPT`` holds for a
stack of layers by kind: ``wte``, ``lm_head``, ``ln_f`` and ``h/attention``
and ``h/window``, each with a leading axis over the layers of its kind in
order (``attn/c_attn`` holds q, k and v side by side, ``mlp/gate`` is the
router over ALL experts, ``mlp/experts/{wg, wi, wo}`` are gate, up and down
of the experts this chip holds).

Float32 throughout; callers wrap it in
``jax.default_matmul_precision("highest")``. A Python loop over the layers
and, inside each, over the held experts with a dense mask. No sort, no
grouped matmul, no scan, no recomputation, no kernel. Attention is walked a
KV head and a block of queries at a time, each block over the keys it can
see and no others, so that no ``[T, T]`` array exists at any length.

The block, for input ``x``:

1. the router's logits ``r = x W_r``, from the block's INPUT, before the
   norm and before attention;
2. ``u = RMSNorm_1(x)``; q (``n_head`` heads), k, v (``n_kv_head`` heads)
   without bias; where the layer is a window layer q and k are rotated
   (half-split pairs over the whole head), elsewhere not at all;
3. key j is seen by query i iff ``j <= i`` and, in a window layer,
   ``i - j < window``; softmax attention, ``n_head / n_kv_head`` query
   heads to a KV head; output projection; residual ``h``;
4. ``m = RMSNorm_2(h)``; the ``top_k`` largest of ``r``; a softmax over
   those logits alone;
5. ReLU-gated experts ``down(relu(gate m) * up m)``, summed under their
   weights over the chosen experts THIS CHIP HOLDS (``first``: the index
   of the first held one among all); residual.

Then the final RMSNorm, the untied head and the mean next-token cross
entropy over the positions that have a next token. There is no auxiliary
loss. ``router_reads`` (``"block"`` as published; ``"mlp"``: ``m``;
``"attended"``: ``h``), ``activation`` and ``window_layers`` exist for the
controls, which are other models.
"""
import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """Half-split rotary embedding over the whole head of [B, T, H, D]."""
    t, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def attend(q, k, v, window, query_block=QUERY_BLOCK, forget=False):
    """Softmax attention of q [B, T, H, D] over k, v [B, T, Hkv, D]: key j
    for query i iff ``0 <= i - j`` (``< window``, where one is given).
    ``forget``: a block's probabilities are computed again in the backward
    pass and not kept (the same arithmetic: the check at thousands of
    positions holds one layer's residuals beside its weights that way)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, t, hkv, h // hkv, d)

    def one(qb, kb, vb, seen):
        s = jnp.einsum("bqgd,bkd->bgqk", qb, kb) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", p, vb)

    if forget:
        one = jax.checkpoint(one)
    rows = []
    for q0 in range(0, t, query_block):
        q1 = min(q0 + query_block, t)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        i = jnp.arange(q0, q1)[:, None]
        j = jnp.arange(k0, q1)[None, :]
        seen = (j <= i) if window is None else (j <= i) & (i - j < window)
        rows.append(jnp.stack(
            [one(q[:, q0:q1, g], k[:, k0:q1, g], v[:, k0:q1, g], seen)
             for g in range(hkv)], axis=2))
    return jnp.concatenate(rows, axis=1).reshape(b, t, h * d)


def attention(u, p, *, n_head, n_kv_head, head_dim, window, rotate, theta,
              forget=False):
    b, t, _ = u.shape
    qkv = u @ p["c_attn"]["kernel"]
    hd, kd = n_head * head_dim, n_kv_head * head_dim
    q = qkv[..., :hd].reshape(b, t, n_head, head_dim)
    k = qkv[..., hd:hd + kd].reshape(b, t, n_kv_head, head_dim)
    v = qkv[..., hd + kd:].reshape(b, t, n_kv_head, head_dim)
    if rotate:
        q, k = rotary(q, theta), rotary(k, theta)
    return attend(q, k, v, window, forget=forget) @ p["c_proj"]["kernel"]


def route(x, kernel, top_k):
    """``(weights [N, E], chosen [N, E] bool)`` of the router's input ``x``
    [N, C]: a softmax over each token's ``top_k`` largest logits, zero
    elsewhere."""
    logits = x @ kernel
    n, e = logits.shape
    top, idx = jax.lax.top_k(logits, top_k)
    chosen = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None], idx].set(True)
    weights = jnp.zeros((n, e), logits.dtype).at[
        jnp.arange(n)[:, None], idx].set(jax.nn.softmax(top, axis=-1))
    return weights, chosen


ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def experts(m, weights, p, first, activation="relu"):
    """The held experts on every token, weighted; [N, C] -> [N, C]."""
    act = ACTIVATIONS[activation]
    y = jnp.zeros_like(m)
    for e in range(p["wi"].shape[0]):
        hidden = act(m @ p["wg"][e]) * (m @ p["wi"][e])
        y = y + weights[:, first + e:first + e + 1] * (hidden @ p["wo"][e])
    return y


def embed(params, ids):
    return params["wte"]["embedding"][ids]


def block(x, p, *, window, rotate, n_head, n_kv_head, head_dim, top_k, first,
          eps=1e-6, theta=1.5e6, router_reads="block", activation="relu",
          forget=False):
    """One layer on [B, T, C] with its parameters ``p`` (no layer axis):
    ``(x, chosen [B*T, E] bool)``."""
    b, t, c = x.shape
    h = x + attention(rms_norm(x, p["ln_1"]["scale"], eps), p["attn"],
                      n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
                      window=window, rotate=rotate, theta=theta,
                      forget=forget)
    m = rms_norm(h, p["ln_2"]["scale"], eps)
    scored = {"block": x, "mlp": m, "attended": h}[router_reads]
    w, chosen = route(scored.reshape(b * t, c), p["mlp"]["gate"]["kernel"],
                      top_k)
    y = experts(m.reshape(b * t, c), w, p["mlp"]["experts"], first,
                activation)
    return h + y.reshape(x.shape), chosen


def head_loss(x, ln_f, lm_head, ids, eps=1e-6):
    """Mean next-token cross entropy of the final hidden states (labels
    are the inputs, shifted), and the logits."""
    logits = rms_norm(x, ln_f["scale"], eps) @ lm_head
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1)), logits


def layers(params, layer_types):
    """``(kind, parameters)`` of each layer in order, out of the stacks by
    kind."""
    at = dict.fromkeys(set(layer_types), 0)
    for kind in layer_types:
        yield kind, jax.tree.map(lambda a, i=at[kind]: a[i],
                                 params["h"][kind])
        at[kind] += 1


def forward(params, ids, *, layer_types, window, window_layers=True,
            rotate_full=False, **kw):
    """``(cross entropy, logits [B, T, V], chosen [L, B*T, E] bool)`` of the
    float32 parameter tree ``params`` on the token ids [B, T].
    ``layer_types``: ``"window"`` | ``"attention"`` a layer."""
    eps = kw.get("eps", 1e-6)
    x = embed(params, ids)
    chosen = []
    for kind, p in layers(params, layer_types):
        windowed = kind == "window"
        x, c = block(x, p, window=window if windowed and window_layers
                     else None, rotate=windowed or rotate_full, **kw)
        chosen.append(c)
    ce, logits = head_loss(x, params["ln_f"], params["lm_head"], ids, eps)
    return ce, logits, jnp.stack(chosen)


def loss(params, ids, **kw):
    ce, _, chosen = forward(params, ids, **kw)
    return ce, chosen


def loss_and_grads(params, ids, **kw):
    """``(loss, chosen, grads)`` in float32 at ``highest`` matmul
    precision; ``params`` may hold any float dtype and is cast."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        (value, chosen), grads = jax.value_and_grad(loss, has_aux=True)(
            params, ids, **kw)
    return value, chosen, grads
