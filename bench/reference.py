"""Plain float32 reference of a cell's first training steps.

It imports nothing of the program. From the configuration file's numbers it
builds its own parameter template, takes the weights from the benchmark's
generator (``weights.py``) and the tokens from a copy of the public-seed
token stream, and follows the cell's first steps in straightforward
``jax.numpy``: per-peer loss and gradient at ``highest`` matmul precision,
the butterfly partitions of each peer's flattened gradient, CenteredClip per
partition, Nesterov SGD, and parameters stored back in their configured
dtype. Layers are rematerialised one at a time, a peer's rows go through in
blocks and the logits in slices, so that it fits beside nothing else on the
chip at any batch.

``mode="fp8"`` is the control: every tensor that the program keeps in its
configured bfloat16 (matmul operands and results, the residual stream, the
SSM's convolution and scan outputs) is kept in float8 instead, values in e4m3 and their gradients in e5m2 with one
scale per tensor: the precision a later change could be tempted to run the
step in. ``fault`` plants the faults the
comparison must catch: ``half_batch`` (each peer's loss over half of its
rows) and ``no_exchange`` (each peer steps with its own gradient).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
FP8, FP8_MAX = jnp.float8_e4m3fn, 448.0
FP8_GRAD, FP8_GRAD_MAX = jnp.float8_e5m2, 57344.0
REF_ROWS = 16  # rows of a peer's batch that one pass of the reference holds
CE_ROWS = 4096  # token rows whose logits the loss holds at once


# ---------------------------------------------------------------- the data
def stream_tokens(data, vocab, seq, batch, step):
    """(batch, seq + 1) int32 tokens of the public-seed stream at ``step``.

    The noisy affine bigram stream x_{t+1} = (a x_t + c) mod V, or a uniform
    token with probability ``noise``, keyed by fold_in(fold_in(key(seed),
    step), peer 0)."""
    a, c = int(data["a"]) % vocab, int(data["c"]) % vocab
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(int(data["global_seed"])), step), 0
    )
    k0, k1, k2 = jax.random.split(key, 3)
    x0 = jax.random.randint(k0, (batch,), 0, vocab)
    noisy = jax.random.bernoulli(k1, data["noise"], (batch, seq))
    rand = jax.random.randint(k2, (batch, seq), 0, vocab)

    def nxt(x, inp):
        nz, rt = inp
        y = jnp.where(nz, rt, (a * x + c) % vocab)
        return y, y

    _, toks = jax.lax.scan(nxt, x0, (noisy.T, rand.T))
    return jnp.concatenate([x0[:, None], toks.T], axis=1).astype(jnp.int32)


# ------------------------------------------------------------- the weights
def template(m):
    """{path: (shape, dtype)} of the parameters, from the config numbers."""
    wdt = m["dtype"]
    f32 = "float32"
    d, V = m["d_model"], m["vocab_size"]
    t = {"embed": ((V, d), wdt), "final_norm/scale": ((d,), f32)}
    if not m["tie_embeddings"]:
        t["lm_head"] = ((d, V), wdt)
    if m["norm"] == "layernorm":
        t["final_norm/bias"] = ((d,), f32)
    if m["learned_pos"]:
        t["pos_embed"] = ((m["max_position"], d), wdt)
    lead = () if m["share_pattern_params"] else (m["n_repeats"],)
    layer = {"norm1/scale": ((d,), f32)}
    if m["norm"] == "layernorm":
        layer["norm1/bias"] = ((d,), f32)
    (mixer, mlp, _cross), = m["pattern"]
    if mixer == "attn_full":
        hd = m["n_heads"] * m["head_dim"]
        kv = m["n_kv_heads"] * m["head_dim"]
        layer.update({
            "mixer/wq": ((d, hd), wdt), "mixer/wk": ((d, kv), wdt),
            "mixer/wv": ((d, kv), wdt), "mixer/wo": ((hd, d), wdt),
        })
    elif mixer == "ssm":
        di = m["ssm_expand"] * d
        N, H = m["ssm_state"], di // m["ssm_head_dim"]
        layer.update({
            "mixer/in_proj": ((d, 2 * di + 2 * N + H), wdt),
            "mixer/out_proj": ((di, d), wdt),
            "mixer/conv_w": ((m["ssm_conv"], di + 2 * N), wdt),
            "mixer/conv_b": ((di + 2 * N,), wdt),
            "mixer/A_log": ((H,), f32), "mixer/D": ((H,), f32),
            "mixer/dt_bias": ((H,), f32), "mixer/gate_norm": ((di,), f32),
        })
    else:
        raise ValueError(f"no reference for mixer {mixer!r}")
    if mlp == "dense":
        layer["norm2/scale"] = ((d,), f32)
        if m["norm"] == "layernorm":
            layer["norm2/bias"] = ((d,), f32)
        layer["mlp/wi"] = ((d, m["d_ff"]), wdt)
        layer["mlp/wdown"] = ((m["d_ff"], d), wdt)
        if m["glu"]:
            layer["mlp/wg"] = ((d, m["d_ff"]), wdt)
    elif mlp != "none":
        raise ValueError(f"no reference for mlp {mlp!r}")
    for k, (shape, dt) in layer.items():
        t["pattern/l0/" + k] = (lead + shape, dt)
    return t


def leaf_order(paths):
    """The order in which a nested dict of parameters flattens."""
    return sorted(paths, key=lambda p: p.split("/"))


# --------------------------------------------------------------- the model
def _qdq(x, dtype, top):
    """Round to a float8 type with one scale per tensor (clipped first: a
    division that rounds up past the type's largest value would give NaN)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    y = jnp.clip(x / scale, -top, top)
    return y.astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _qdq(x, FP8, FP8_MAX)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_qdq(g, FP8_GRAD, FP8_GRAD_MAX),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _store(mode):
    """Where the program keeps a tensor in its configured dtype, the control
    keeps it in float8: e4m3 values, e5m2 gradients (the usual float8
    training recipe), one scale per tensor."""
    return _fp8 if mode == "fp8" else (lambda x: x)


def _matmul(mode):
    q = _store(mode)

    def mm(eq, a, b, wide=False):
        """``wide``: a result the program keeps in float32 (scores)."""
        out = jnp.einsum(eq, q(a), q(b), precision=HIGHEST)
        return out if wide else q(out)

    return mm


def _norm(m, p, x):
    if m["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + m["norm_eps"]) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x**2).mean(-1, keepdims=True) + m["norm_eps"]) * p[
        "scale"
    ]


def _act(m, x):
    if m["act"] == "gelu":
        return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi) * (x + 0.044715 * x**3)))
    return x * jax.nn.sigmoid(x)


def _attention(m, mm, q, p, x):
    B, S, _ = x.shape
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = mm("bsd,de->bse", x, p["wq"]).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", x, p["wk"]).reshape(B, S, K, hd)
    v = mm("bsd,de->bse", x, p["wv"]).reshape(B, S, K, hd)
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, wide=True) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return mm("bse,ed->bsd", o.reshape(B, S, H * hd), p["wo"])


def _segsum(a):
    """(..., T) -> (..., T, T): sum of a[j+1..i] below the diagonal, -inf
    above it."""
    T = a.shape[-1]
    c = jnp.cumsum(a, axis=-1)
    seg = c[..., :, None] - c[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), seg, -jnp.inf)


def _ssd(mm, x, dt, A, Bm, Cm, block):
    """Mamba-2's state-space dual in chunks (arXiv:2405.21060, "SSD minimal"
    listing): y_t = sum_{s<=t} C_t . B_s exp(sum_{s<r<=t} dt_r A) dt_s x_s.

    x (b, l, h, p); dt (b, l, h); A (h,); Bm, Cm (b, l, n)."""
    b, l, h, P = x.shape
    c = l // block
    X = (x * dt[..., None]).reshape(b, c, block, h, P)
    a = jnp.moveaxis((dt * A).reshape(b, c, block, h), 3, 1)  # (b, h, c, L)
    Bc = Bm.reshape(b, c, block, -1)
    Cc = Cm.reshape(b, c, block, -1)
    a_cum = jnp.cumsum(a, axis=-1)
    L = jnp.exp(_segsum(a))  # (b, h, c, L, L)
    G = mm("bcln,bcsn->bcls", Cc, Bc, wide=True)
    y_diag = mm("bhcls,bcshp->bclhp", G[:, None] * L, X)
    decay = jnp.exp(a_cum[..., -1:] - a_cum)  # (b, h, c, L)
    states = mm("bcln,bclhp->bchpn", Bc, X * jnp.moveaxis(decay, 1, 3)[..., None])
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                        precision=HIGHEST)[:, :-1]
    y_off = mm("bcln,bchpn->bclhp", Cc, states) * jnp.moveaxis(
        jnp.exp(a_cum), 1, 3)[..., None]
    return (y_diag + y_off).reshape(b, l, h, P)


def _ssm(m, mm, q, p, x):
    B, S, d = x.shape
    di = m["ssm_expand"] * d
    N, P = m["ssm_state"], m["ssm_head_dim"]
    H = di // P
    zx = mm("bsd,de->bse", x, p["in_proj"])
    z, xs = zx[..., :di], zx[..., di:2 * di]
    conv_in = jnp.concatenate([xs, zx[..., 2 * di:2 * di + 2 * N]], axis=-1)
    dt_raw = zx[..., 2 * di + 2 * N:]
    K = p["conv_w"].shape[0]
    padded = jnp.pad(conv_in, ((0, 0), (K - 1, 0), (0, 0)))
    conv = q(sum(padded[:, i:i + S] * p["conv_w"][i] for i in range(K)) + p["conv_b"])
    conv = q(jax.nn.silu(conv))
    xs = conv[..., :di].reshape(B, S, H, P)
    Bm, Cm = conv[..., di:di + N], conv[..., di + N:]
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    block = min(m["ssm_chunk"], S)
    y = q(_ssd(mm, xs, dt, A, Bm, Cm, block) + p["D"][:, None] * xs)
    y = y.reshape(B, S, di) * jax.nn.silu(z)
    y = y / jnp.sqrt((y**2).mean(-1, keepdims=True) + m["norm_eps"]) * p["gate_norm"]
    return mm("bse,ed->bsd", y, p["out_proj"])


def _nest(flat, prefix):
    out = {}
    for path, v in flat.items():
        if not path.startswith(prefix):
            continue
        node = out
        *head, last = path[len(prefix):].split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def loss_fn(m, mode):
    """``loss(params {path: f32}, tokens (b, S+1)) -> mean cross-entropy``."""
    mm, q = _matmul(mode), _store(mode)
    (mixer, mlp, _), = m["pattern"]

    def block(p, x):
        h = _norm(m, p["norm1"], x)
        mix = _attention if mixer == "attn_full" else _ssm
        x = q(x + mix(m, mm, q, p["mixer"], h))
        if mlp == "dense":
            h = _norm(m, p["norm2"], x)
            u = mm("bsd,df->bsf", h, p["mlp"]["wi"])
            if "wg" in p["mlp"]:
                u = _act(m, mm("bsd,df->bsf", h, p["mlp"]["wg"])) * u
            else:
                u = _act(m, u)
            x = q(x + mm("bsf,fd->bsd", u, p["mlp"]["wdown"]))
        return x

    def loss(params, toks):
        inp, tgt = toks[:, :-1], toks[:, 1:]
        S = inp.shape[1]
        x = params["embed"][inp]
        if m["learned_pos"]:
            x = x + params["pos_embed"][:S]
        x = q(x)
        layer = _nest(params, "pattern/l0/")
        body = jax.checkpoint(lambda x, p: (block(p, x), None))
        if m["share_pattern_params"]:
            x, _ = jax.lax.scan(lambda x, _: body(x, layer), x, None,
                                length=m["n_repeats"])
        else:
            x, _ = jax.lax.scan(body, x, layer)
        x = _norm(m, _nest(params, "final_norm/"), x)
        rows = tgt.size
        blk = math.gcd(rows, CE_ROWS)

        @jax.checkpoint
        def ce(args):
            xb, tb = args
            if m["tie_embeddings"]:
                logits = mm("nd,vd->nv", xb, params["embed"])
            else:
                logits = mm("nd,dv->nv", xb, params["lm_head"])
            lse = jax.nn.logsumexp(logits, axis=-1)
            return (lse - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]).sum()

        parts = jax.lax.map(ce, (x.reshape(rows // blk, blk, -1),
                                 tgt.reshape(rows // blk, blk)))
        return parts.sum() / rows

    return loss


# ----------------------------------------------------------- the aggregate
def centered_clip(xs, tau, n_iters):
    """CenteredClip from v = 0: v += mean_i min(1, tau/|x_i - v|)(x_i - v).
    xs: (n, k)."""
    v = jnp.zeros(xs.shape[1], jnp.float32)
    for _ in range(n_iters):
        diff = xs - v
        w = jnp.minimum(1.0, tau / jnp.maximum(jnp.linalg.norm(diff, axis=1), 1e-30))
        v = v + (w[:, None] * diff).mean(0)
    return v


def butterfly_clip(grads, order, tau, n_iters):
    """The butterfly aggregate of n peers' gradients {path: f32}: each
    flattened in ``order`` and cut into n contiguous partitions; partition j
    is CenteredClip over the n peers' copies of it."""
    n = len(grads)
    if n == 1:
        # one vector: the iterate stays on the segment from 0 to x, so
        # v = c x with a scalar c that follows the same rule
        g = grads[0]
        norm = jnp.sqrt(sum(jnp.sum(g[p] ** 2) for p in order))
        c = jnp.float32(0.0)
        for _ in range(n_iters):
            r = (1 - c) * norm
            c = c + jnp.minimum(1.0, tau / jnp.maximum(r, 1e-30)) * (1 - c)
        return {p: c * g[p] for p in order}
    flat = jnp.stack([jnp.concatenate([g[p].ravel() for p in order]) for g in grads])
    d = flat.shape[1]
    part = -(-d // n)
    flat = jnp.pad(flat, ((0, 0), (0, part * n - d))).reshape(n, n, part)
    agg = jnp.concatenate(
        [centered_clip(flat[:, j], tau, n_iters) for j in range(n)]
    )[:d]
    out, off = {}, 0
    for p in order:
        size = grads[0][p].size
        out[p] = agg[off:off + size].reshape(grads[0][p].shape)
        off += size
    return out


# ------------------------------------------------------------- the steps
def follow(config, traffic, seed, base_step, n_steps=4, mode="f32", fault=None):
    """The reference's first ``n_steps`` steps of a cell from ``seed``.

    Returns {"loss": [per step], "grad0": {path: |g0|}, "update": {path:
    |p_n - p_0|}, "grad0_vec": {path: g0 on the host}} with g0 the first
    aggregate as the optimizer takes it and the change as the stored
    parameters keep it."""
    m = config["model"]
    tr = traffic
    n = tr["mesh"][0]
    b, S = tr["per_peer_batch"], tr["seq"]
    opt = tr["optimizer"]
    lr, mu = opt["lr"], opt["momentum"]
    clip = tr["aggregator"]
    devices = jax.devices()[:n]  # each peer's gradient on a chip of its own
    tmpl = template(m)
    order = leaf_order(tmpl)
    gen = weights.make_generator(tmpl)
    key_data = weights.seed_key_data(seed)
    loss = loss_fn(m, mode)

    @jax.jit
    def peer(stored, toks):
        """Mean loss and gradient over the peer's rows, in blocks of rows."""
        if fault == "half_batch":
            toks = toks[: toks.shape[0] // 2]
        p32 = {k: v.astype(jnp.float32) for k, v in stored.items()}
        rows = toks.shape[0]
        blk = math.gcd(rows, REF_ROWS)

        def acc(total, tb):
            return jax.tree.map(jnp.add, total,
                                jax.value_and_grad(loss)(p32, tb)), None

        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, p32))
        total, _ = jax.lax.scan(acc, zero, toks.reshape(rows // blk, blk, -1))
        return jax.tree.map(lambda x: x / (rows // blk), total)

    @jax.jit
    def update(stored, mom, agg):
        new_m = {p: mu * mom[p] + agg[p] for p in order}
        new_s = {
            p: (stored[p].astype(jnp.float32)
                - lr * (agg[p] + mu * new_m[p] if opt["nesterov"] else new_m[p])
                ).astype(stored[p].dtype)
            for p in order
        }
        return new_s, new_m

    norms = jax.jit(lambda t: {p: jnp.linalg.norm(t[p].astype(jnp.float32).ravel())
                               for p in order})
    change = jax.jit(lambda a, z: {
        p: jnp.linalg.norm((a[p].astype(jnp.float32) - z[p].astype(jnp.float32)).ravel())
        for p in order})
    tokens = jax.jit(lambda step: stream_tokens(
        tr["data"], m["vocab_size"], S, n * b, step))
    aggregate = jax.jit(lambda gs: butterfly_clip(gs, order, clip["tau"], clip["n_iters"]))

    stored = jax.device_put(gen(key_data), devices[0])
    mom = {p: jnp.zeros(tmpl[p][0], jnp.float32) for p in order}
    losses, grad0 = [], None
    for t in range(n_steps):
        toks = tokens(base_step + t)
        outs = [peer(jax.device_put(stored, dev),
                     jax.device_put(toks[i * b:(i + 1) * b], dev))
                for i, dev in enumerate(devices)]
        losses.append(float(np.mean([float(o[0]) for o in outs])))
        grads = [jax.device_put(o[1], devices[0]) for o in outs]
        del outs
        if fault == "no_exchange":
            agg = grads[0]
        else:
            agg = aggregate(grads)
        del grads
        if t == 0:
            grad0 = {p: float(v) for p, v in norms(agg).items()}
            grad0_vec = jax.device_get(agg)
        stored, mom = update(stored, mom, agg)
        del agg
    del mom
    start = jax.device_put(gen(key_data), devices[0])
    upd = {p: float(v) for p, v in change(stored, start).items()}
    return {"loss": losses, "grad0": grad0, "update": upd,
            "grad0_vec": grad0_vec}
