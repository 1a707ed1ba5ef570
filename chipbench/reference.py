"""Plain reference of MU-SplitFed rounds (Algorithm 1), for the check that
decides ``correct``.

It imports nothing of the program. It follows the model and the algorithm
as they are published and as the program states them:

  * the dense decoder: pre-norm blocks of grouped-query attention with
    rotary positions (interleaved pairs, theta from the config) and a
    SwiGLU MLP; a non-parametric LayerNorm or an RMSNorm (eps 1e-5); a
    final norm and an untied head; loss = mean next-token cross-entropy;
  * the split after ``cut`` layers: the client holds the embedding and
    layers [0, cut), the server layers [cut, L), the final norm and the
    head;
  * one round: per client m, with mkey = fold_in(fold_in(key, r), m), the
    three client forwards h, h+, h- at x_c +- eps*u(fold_in(mkey, 0)); the
    loss at the round start; tau SPSA steps of the server copy on h, step i
    perturbed by z(fold_in(fold_in(fold_in(mkey, 1), i), p)); the
    ZO-backprop pair delta_c = F(x_s^tau, h+) - F(x_s^tau, h-); then
    every server and client record is replayed into the round's
    parameters with weight lr_global * w_m, w_m = client m's schedule mask
    over the mask's sum (1 / M with every client in);
  * the noise of a record: leaf i of a half (in the sorted-key order of
    ``jax.tree.flatten``) draws N(0, 1) from threefry under
    fold_in(key, i) ('gaussian'), or from the murmur3 / Box-Muller counter
    hash of (key[0] ^ key[1] ^ i * 0x9E3779B9, element index) ('counter').

Everything runs in float32 with ``highest`` matmul precision; or, for the
control, one step below the program's bfloat16: every matmul operand
rounded to float8 (e4m3) under a per-tensor scale (per layer for stacked
layers), products summed in float32, and every parameter the program holds
in bfloat16 held in bfloat16 (rounded after each replay), as a program
that ran its matmuls in float8 would. Perturbed weights are never stored:
each layer's weights are rebuilt inside the layer scan from the round's
parameters and the records so far, so the reference holds one float32 copy
of the model and a layer's temporaries.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

LANE = 1024
_SALT = 0x9E3779B9
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLD = np.uint32(0x9E3779B9)
F8_MAX = 240.0        # largest float8 value with 4 exponent, 3 mantissa bits


# ---------------------------------------------------------------------------
# noise: element n of a leaf, computed from the flat index alone, so a
# layer's slice of a stacked leaf is made without the rest of the leaf
# ---------------------------------------------------------------------------

def _flat_index(shape, layer):
    """uint32 row-major indices of leaf[layer] (layer=None: the whole leaf)."""
    if int(np.prod(shape)) >= 2 ** 32:
        raise ValueError(f"leaf {shape} has 2**32 elements or more")
    inner = shape[1:] if layer is not None else shape
    n_inner = int(np.prod(inner)) if inner else 1
    idx = jax.lax.iota(jnp.uint32, n_inner).reshape(inner)
    if layer is not None:
        idx = idx + jnp.asarray(layer, jnp.uint32) * jnp.uint32(n_inner)
    return idx


def _threefry_normal(key, idx):
    """jax.random.normal(key, shape) at flat indices ``idx``
    (partitionable threefry: the bits of element n are a function of n)."""
    k1 = jnp.broadcast_to(key[0], idx.shape)
    k2 = jnp.broadcast_to(key[1], idx.shape)
    b1, b2 = threefry2x32_p.bind(k1, k2, jnp.zeros_like(idx), idx)
    bits = (b1 ^ b2) >> 9 | jnp.uint32(0x3F800000)
    f = jax.lax.bitcast_convert_type(bits, jnp.float32) - 1.0
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jnp.maximum(lo, f * (np.float32(1.0) - lo) + lo)
    return np.float32(math.sqrt(2.0)) * jax.lax.erf_inv(u)


def _hash(seed, idx):
    x = idx * _GOLD + seed
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    return x ^ (x >> 16)


def _counter_normal(seed, idx):
    """Box-Muller over two murmur3 hashes of (seed, row, lane)."""
    mixed = (idx // jnp.uint32(LANE)) * _M1 + seed
    lo = idx % jnp.uint32(LANE)
    h1 = _hash(mixed, lo)
    h2 = _hash(mixed ^ np.uint32(0xA5A5A5A5), lo)
    u1 = ((h1 >> 8).astype(jnp.int32).astype(jnp.float32) + 1.0) \
        * np.float32(1.0 / 16777216.0)
    u2 = (h2 >> 8).astype(jnp.int32).astype(jnp.float32) \
        * np.float32(1.0 / 16777216.0)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        2.0 * np.float32(np.pi) * u2)


def _counter_seed(key, leaf):
    """key[0] ^ key[1] ^ leaf * 0x9E3779B9 (mod 2**32); leaf may be traced."""
    return (key[0] ^ key[1]) ^ (jnp.asarray(leaf, jnp.uint32)
                                * jnp.uint32(_SALT))


def noise(dist: str, key, leaf, shape, layer=None):
    """u(key) of leaf ``leaf`` (full shape ``shape``), or its [layer] slice."""
    idx = _flat_index(tuple(shape), layer)
    if dist == "gaussian":
        return _threefry_normal(jax.random.fold_in(key, leaf), idx)
    if dist == "counter":
        return _counter_normal(_counter_seed(key, leaf), idx)
    raise ValueError(f"noise {dist!r}")


# ---------------------------------------------------------------------------
# the model, in float32 (or float8 operands for the control)
# ---------------------------------------------------------------------------

def to_fp8(x):
    """x rounded to float8 (4 exponent and 3 mantissa bits) under a
    per-tensor scale, per layer where x is a stack of matrices (ndim 3).
    ``reduce_precision`` rounds in float32 arithmetic: a round trip through
    a float8 dtype can be folded away by the compiler."""
    axes = tuple(range(1, x.ndim)) if x.ndim == 3 else None
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True),
                    1e-30) / F8_MAX
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def make_mm(precision: str):
    """einsum for the forward: float32 at 'highest', or with both operands
    rounded to float8 first (products summed in float32)."""
    if precision not in ("f32", "fp8"):
        raise ValueError(precision)
    q = to_fp8 if precision == "fp8" else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q(a), q(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return mm


def _norm(model, p, x, key):
    if model["norm_type"] == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-5) * p[key]["scale"]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + 1e-5)
    if model["norm_type"] == "layernorm":
        out = out * p[key]["scale"] + p[key]["bias"]
    return out


def _rope(x, theta):
    """x: (B, H, S, dh); rotates interleaved pairs (2j, 2j+1)."""
    S, dh = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def block(model, mm, p, x):
    """One pre-norm decoder layer; p holds this layer's float32 weights."""
    B, S, d = x.shape
    H, Hkv = model["n_heads"], model["n_kv_heads"]
    dh = model["d_head"]
    G = H // Hkv
    h = _norm(model, p, x, "norm1")
    a = p["core"]
    q = mm("bsd,de->bse", h, a["wq"]).reshape(B, S, H, dh).swapaxes(1, 2)
    k = mm("bsd,de->bse", h, a["wk"]).reshape(B, S, Hkv, dh).swapaxes(1, 2)
    v = mm("bsd,de->bse", h, a["wv"]).reshape(B, S, Hkv, dh).swapaxes(1, 2)
    q = _rope(q, model["rope_theta"]).reshape(B, Hkv, G, S, dh)
    k = _rope(k, model["rope_theta"])
    s = mm("bkgsd,bktd->bkgst", q, k) / np.float32(math.sqrt(dh))
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = mm("bkgst,bktd->bskgd", pr, v).reshape(B, S, H * dh)
    x = x + mm("bse,ed->bsd", o, a["wo"])
    h2 = _norm(model, p, x, "norm2")
    f = p["ffn"]
    u = jax.nn.silu(mm("bsd,df->bsf", h2, f["wi"])) \
        * mm("bsd,df->bsf", h2, f["wg"])
    return x + mm("bsf,fd->bsd", u, f["wo"])


def cross_entropy(mm, x, head, labels, rows: int = 256):
    """Mean next-token CE; logits made ``rows`` positions at a time."""
    B, S, d = x.shape
    rows = math.gcd(rows, B * S)
    xs = x.reshape(-1, rows, d)
    ls = labels.reshape(-1, rows)

    def one(args):
        xc, lc = args
        logits = mm("td,dv->tv", xc, head)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(lz - gold)

    return jnp.sum(jax.lax.map(one, (xs, ls))) / (B * S)


# ---------------------------------------------------------------------------
# points of the form  base + sum_j a_j u(key_j)  (+- eps z(pkey))
# ---------------------------------------------------------------------------

def _leaf_table(half):
    """[(path, leaf index, full shape)] in jax.tree.flatten order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(half)
    return [(tuple(getattr(k, "key", k) for k in path), i, x.shape)
            for i, (path, x) in enumerate(flat)]


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _shifted(dist, base, leaf, shape, layer, keys, coefs):
    """base + sum_j coefs[j] * u(keys[j]) for one leaf (or its layer)."""
    def add(j, w):
        return jax.lax.cond(
            coefs[j] != 0.0,
            lambda w: w + coefs[j] * noise(dist, keys[j], leaf, shape, layer),
            lambda w: w, w)
    return jax.lax.fori_loop(0, coefs.shape[0], add, base)


def _pm(dist, w, leaf, shape, layer, pkey, peps):
    """(w + peps z, w - peps z); no noise is made where peps is 0."""
    z = jax.lax.cond(peps != 0.0,
                     lambda: noise(dist, pkey, leaf, shape, layer),
                     lambda: jnp.zeros(w.shape, jnp.float32))
    return w + peps * z, w - peps * z


def split(params, cut: int):
    """(client, server): the embedding and layers [0, cut); layers
    [cut, L), the final norm and the head."""
    units = params["units"]
    client = {"embed": params["embed"],
              "units": jax.tree.map(lambda a: a[:cut], units)}
    server = {"final_norm": params["final_norm"],
              "lm_head": params["lm_head"],
              "units": jax.tree.map(lambda a: a[cut:], units)}
    return client, server


class Reference:
    """Follows MU-SplitFed rounds from given initial weights, holding the
    model as its two float32 halves."""

    def __init__(self, model: Dict, cut: int, traffic: Dict,
                 precision: str = "f32"):
        self.model, self.cut, self.t = model, cut, traffic
        self.dist = traffic["noise"]
        self.mm = make_mm(precision)
        self.lowp_state = precision == "fp8"
        self._halves = jax.jit(lambda p: self.split(
            jax.tree.map(lambda x: x.astype(jnp.float32), p)))
        self._client3 = jax.jit(self._client3_impl)
        self._server1 = jax.jit(self._server1_impl)
        self._server2 = jax.jit(self._server2_impl)
        self._apply = jax.jit(self._apply_impl, static_argnums=(4,),
                              donate_argnums=(0,))

    def split(self, params):
        return split(params, self.cut)

    def _plain(self, half, x):
        """The half's layers, as they stand, on one activation."""
        table = [t for t in _leaf_table(half) if t[0][0] == "units"]

        def body(x, ws):
            p = {}
            for (path, _, _), w in zip(table, ws):
                _set(p, path[2:], w)
            return block(self.model, self.mm, p, x), None

        ws = [_get(half, path) for path, _, _ in table]
        return jax.lax.scan(body, x, ws)[0]

    def _stack(self, half, keys, coefs, pkey, peps, x_pair):
        """The half's layers on a pair of activations, the first at
        (half + shifts + peps z), the second at (half + shifts - peps z)."""
        table = [t for t in _leaf_table(half) if t[0][0] == "units"]
        mm, model, dist = self.mm, self.model, self.dist

        def body(xab, ly):
            layer, ws = ly
            pa, pb = {}, {}
            for (path, leaf, shape), w in zip(table, ws):
                w = _shifted(dist, w, leaf, shape, layer, keys, coefs)
                wa, wb = _pm(dist, w, leaf, shape, layer, pkey, peps)
                _set(pa, path[2:], wa)
                _set(pb, path[2:], wb)
            xa, xb = xab
            return (block(model, mm, pa, xa), block(model, mm, pb, xb)), None

        n = jax.tree.leaves(half["units"])[0].shape[0]
        ws = [_get(half, path) for path, _, _ in table]
        out, _ = jax.lax.scan(body, x_pair,
                              (jnp.arange(n, dtype=jnp.uint32), ws))
        return out

    def _client3_impl(self, client, ukey, eps, tokens):
        """(h, h+, h-) at x_c and x_c +- eps u(ukey)."""
        table = _leaf_table(client)
        (_, eleaf, eshape), = [t for t in table if t[0] == ("embed",)]
        d = eshape[1]
        e = client["embed"][tokens]
        idx = tokens.astype(jnp.uint32)[..., None] * jnp.uint32(d) \
            + jax.lax.iota(jnp.uint32, d)
        if self.dist == "gaussian":
            u = _threefry_normal(jax.random.fold_in(ukey, eleaf), idx)
        else:
            u = _counter_normal(_counter_seed(ukey, eleaf), idx)
        none = jnp.zeros((1,), jnp.float32), jnp.zeros((1, 2), jnp.uint32)
        hp, hm = self._stack(client, none[1], none[0], ukey, eps,
                             (e + eps * u, e - eps * u))
        return self._plain(client, e), hp, hm

    def _head_loss(self, p, x, labels):
        p.setdefault("final_norm", {})
        return cross_entropy(self.mm, _norm(self.model, p, x, "final_norm"),
                             p["lm_head"], labels)

    def _server1_impl(self, server, h, labels):
        """F(s, h): the loss at the server's weights as they stand."""
        rest = {k: v for k, v in server.items() if k != "units"}
        return self._head_loss(rest, self._plain(server, h), labels)

    def _server2_impl(self, server, keys, coefs, pkey, peps, ha, hb, labels):
        """(F(s + shifts + peps z, ha), F(s + shifts - peps z, hb))."""
        dist = self.dist
        xa, xb = self._stack(server, keys, coefs, pkey, peps, (ha, hb))
        pa, pb = {}, {}
        for path, leaf, shape in _leaf_table(server):
            if path[0] == "units":
                continue
            w = _shifted(dist, _get(server, path), leaf, shape, None, keys,
                         coefs)
            wa, wb = _pm(dist, w, leaf, shape, None, pkey, peps)
            _set(pa, path, wa)
            _set(pb, path, wb)
        return (self._head_loss(pa, xa, labels),
                self._head_loss(pb, xb, labels))

    def _apply_impl(self, w, keys, leaf, coefs, lowp):
        """w + sum_j coefs[j] u(keys[j]) over a whole leaf, rounded to
        bfloat16 where ``lowp`` (the control, on a leaf the program holds in
        bfloat16)."""
        w = _shifted(self.dist, w, leaf, w.shape, None, keys, coefs)
        return w.astype(jnp.bfloat16).astype(jnp.float32) if lowp else w

    def round(self, client, server, batch, rkey, weights):
        """One round; client m's records weigh ``weights[m]`` (its mask over
        the mask's sum). Returns (client, server, the round-start loss: the
        weighted mean over clients of F(x_s, h), each client's F(x_s, h),
        nan for a client left out)."""
        t = self.t
        M, tau, P = t["clients"], t["tau"], t["perturbations"]
        eps = jnp.float32(t["zo_eps"])
        ukeys, skeys = round_keys(rkey, M, tau, P)
        s_coefs, c_coefs, losses = [], [], []
        for m in range(M):
            if weights[m] == 0.0:   # left out: its records apply nothing
                s_coefs += [0.0] * (tau * P)
                c_coefs.append(0.0)
                losses.append(float("nan"))
                continue
            tok = jnp.asarray(batch["tokens"][m])
            lab = jnp.asarray(batch["labels"][m])
            h, hp, hm = self._client3(client, ukeys[m], eps, tok)
            losses.append(float(self._server1(server, h, lab)))
            keys = np.zeros((tau * P, 2), np.uint32)
            coefs = np.zeros(tau * P, np.float32)
            for i in range(tau):
                step = []
                for p in range(P):
                    pk = skeys[m, i, p]
                    lp, lm = self._server2(server, jnp.asarray(keys),
                                           jnp.asarray(coefs), pk, eps,
                                           h, h, lab)
                    step.append((pk, float(lp) - float(lm)))
                for p, (pk, dd) in enumerate(step):
                    c = np.float32(t["lr_server"] * dd / (2 * t["zo_eps"] * P))
                    keys[i * P + p] = pk
                    coefs[i * P + p] = -c
                    s_coefs.append(c * weights[m])
            la, lb = self._server2(server, jnp.asarray(keys),
                                   jnp.asarray(coefs), ukeys[m],
                                   jnp.float32(0.0), hp, hm, lab)
            cc = t["lr_client"] * (float(la) - float(lb)) / (2 * t["zo_eps"])
            c_coefs.append(cc * weights[m])
        g = t["lr_global"]
        c_coefs = [-g * c for c in c_coefs]
        s_coefs = [-g * c for c in s_coefs]
        s_keys = skeys.reshape(-1, 2)
        client = self.replay(client, ukeys, c_coefs, self.lowp[0])
        server = self.replay(server, s_keys, s_coefs, self.lowp[1])
        return (client, server, float(np.dot(weights, np.nan_to_num(losses))),
                losses)

    def replay(self, half, keys, coefs, lowp):
        """half + sum_j coefs[j] u(keys[j]), leaf by leaf, in place."""
        keys = jnp.asarray(np.stack(keys))
        coefs = jnp.asarray(np.asarray(coefs, np.float32))
        flat, treedef = jax.tree.flatten(half)
        out = []
        for i in range(len(flat)):
            w, flat[i] = flat[i], None
            out.append(self._apply(w, keys, jnp.uint32(i), coefs,
                                   self.lowp_state and lowp[i]))
        return jax.tree.unflatten(treedef, out)

    def follow(self, params, key, batch_fn, masks):
        """Run rounds [0, len(masks)) from ``params`` (the program's layout,
        any float type; the caller hands it over); round r weighs client m
        by masks[r][m] / sum(masks[r]). Returns (client, server, [per-round
        loss], [per-round [per-client round-start loss]])."""
        self.lowp = [[x.dtype == jnp.bfloat16 for x in jax.tree.leaves(h)]
                     for h in self.split(params)]
        client, server = self._halves(params)
        params = None
        losses, client_losses = [], []
        with jax.default_matmul_precision("highest"):
            for r, mask in enumerate(masks):
                mask = np.asarray(mask, np.float64)
                client, server, loss, per_client = self.round(
                    client, server, batch_fn(r), jax.random.fold_in(key, r),
                    mask / max(float(mask.sum()), 1.0))
                losses.append(loss)
                client_losses.append(per_client)
        return client, server, losses, client_losses


def round_keys(rkey, M: int, tau: int, P: int):
    """The records' keys of a round: client m's u key (M, 2), and its
    server step i, perturbation p key (M, tau, P, 2)."""
    ukeys = np.zeros((M, 2), np.uint32)
    skeys = np.zeros((M, tau, P, 2), np.uint32)
    for m in range(M):
        mkey = jax.random.fold_in(rkey, m)
        ukeys[m] = np.asarray(jax.random.fold_in(mkey, 0))
        skey = jax.random.fold_in(mkey, 1)
        for i in range(tau):
            ki = jax.random.fold_in(skey, i)
            for p in range(P):
                skeys[m, i, p] = np.asarray(jax.random.fold_in(ki, p))
    return ukeys, skeys
