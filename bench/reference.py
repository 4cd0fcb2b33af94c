"""Plain reference of one closed-loop engine window, from the seed.

What the federation computes in its first window, written out step by step
in ``jax.numpy`` and ``numpy`` from the published description and the
configuration file, with nothing taken from the program under test:

* the paper's residual CNN (3x3 convolutions, two residual blocks per stage,
  stride-2 projection at the stage change, mean pool, linear head), its
  initialisation from the run's key, and its sample-weighted cross-entropy;
* each client's local round: ``local_epochs * local_rounds`` passes over its
  shard, shuffled by a key per pass, in mini-batches of ``batch_size``
  (the tail that does not fill a batch is left out), plain SGD at ``lr``;
  the effective gradient is (start - end) / lr;
* the Eq. 1 value of each report, ||g_prev - g||^2 * (1 + N / 1000)^Acc,
  with Acc the client's accuracy on the whole test set;
* the arrival order of the window, from the paper testbed's service times
  (lognormal, sigma 0.15, around 1 / 4.5 / 3.5 time units), drawn from a
  splitmix64 counter hash of (seed, stream, client, draw);
* the VAFL gate (upload when the value is at least the mean of every
  finite value reported so far, and when none is), top-k + stochastic
  int8 compression of the upload with
  error feedback, and the FedBuff buffer of K reconstructions mixed with
  staleness weights (1 + tau)^-1/2 at rate 0.5.

Matrix products run at ``Precision.HIGHEST`` in float32.  ``dtype`` and
``precision`` can be lowered: the control computes all of this in bfloat16.
"""
from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# ---------------------------------------------------------------- model ---


def cnn_init(model: dict, key):
    """Parameters in the order and from the keys the architecture's
    initialiser uses: a counter folded into the key once per tensor
    (biases included); conv weights N(0, 2 / fan_in), head N(0, 1 / c)."""
    count = [0]

    def nxt():
        count[0] += 1
        return jax.random.fold_in(key, count[0])

    def conv(cin, cout, k=3):
        w = (jax.random.normal(nxt(), (k, k, cin, cout))
             * (2.0 / (k * k * cin)) ** 0.5).astype(jnp.float32)
        nxt()
        return {"w": w, "b": jnp.zeros((cout,), jnp.float32)}

    chans = model["channels"]
    params = {"stem": conv(1, chans[0]), "blocks": [], "proj": []}
    cin = chans[0]
    for c in chans:
        for _ in range(model["num_blocks"]):
            params["blocks"].append({
                "c1": conv(cin, c), "c2": conv(c, c),
                "proj": conv(cin, c, k=1) if cin != c else None})
            cin = c
    w = (jax.random.normal(nxt(), (cin, model["num_classes"]))
         * (1.0 / cin ** 0.5)).astype(jnp.float32)
    nxt()
    params["head"] = {"w": w,
                      "b": jnp.zeros((model["num_classes"],), jnp.float32)}
    return params


THREE_PASS = "bf16x3"


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three(f, a, b):
    """f(a, b) from bfloat16 halves: hi*hi + hi*lo + lo*hi."""
    ah, al = _split(a)
    bh, bl = _split(b)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def three_pass(f):
    """A bilinear product ``f`` (computed exactly) at three bfloat16 passes
    with float32 accumulation, in the forward and the backward pass: what
    ``Precision.HIGH`` computes on a TPU, spelled out for a backend that
    computes every float32 product exactly whatever the precision."""
    @jax.custom_vjp
    def g(a, b):
        return _three(f, a, b)

    def fwd(a, b):
        return _three(f, a, b), (a, b)

    def bwd(res, ct):
        a, b = res
        da = _three(lambda c, bb: jax.vjp(lambda aa: f(aa, bb), a)[1](c)[0],
                    ct, b)
        db = _three(lambda aa, c: jax.vjp(lambda bb: f(aa, bb), b)[1](c)[0],
                    a, ct)
        return da, db

    g.defvjp(fwd, bwd)
    return g


def forward(params, images, precision=HIGHEST):
    """images (B, 28, 28) -> logits (B, classes), in the params' dtype.
    ``precision`` is a ``jax.lax.Precision`` or ``THREE_PASS``."""
    dt = params["stem"]["w"].dtype
    x = images.astype(dt)[..., None]
    exact = HIGHEST if precision == THREE_PASS else precision

    def product(f):
        return three_pass(f) if precision == THREE_PASS else f

    def conv(p, x, stride=1):
        y = product(lambda a, w: jax.lax.conv_general_dilated(
            a, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=exact))(x, p["w"])
        return y + p["b"]

    x = jax.nn.relu(conv(params["stem"], x))
    for bp in params["blocks"]:
        stride = 2 if bp["proj"] is not None else 1
        h = jax.nn.relu(conv(bp["c1"], x, stride))
        h = conv(bp["c2"], h)
        sc = x if bp["proj"] is None else conv(bp["proj"], x, stride)
        x = jax.nn.relu(h + sc)
    x = jnp.mean(x, axis=(1, 2))
    return product(lambda a, w: jnp.dot(a, w, precision=exact))(
        x, params["head"]["w"]) + params["head"]["b"]


def loss(params, images, labels, weights, precision=HIGHEST):
    """Sample-weighted mean cross-entropy."""
    logits = forward(params, images, precision).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)


# ---------------------------------------------------------- local round ---


def local_round(params, images, labels, mask, key, local: dict,
                precision=HIGHEST):
    """One client's local round -> (new params, effective gradient)."""
    m = images.shape[0]
    b = min(local["batch_size"], m)
    nb = max(m // b, 1)
    lr = local["lr"]
    passes = local["local_epochs"] * local["local_rounds"]
    keys = jax.random.split(key, passes + 1)[:-1]

    def one_pass(p, k):
        perm = jax.random.permutation(k, m)[:nb * b]
        w = mask[perm].reshape(nb, b)
        if local.get("half_batch"):
            w = w * (jnp.arange(b) < b // 2)
        batches = (images[perm].reshape(nb, b, *images.shape[1:]),
                   labels[perm].reshape(nb, b), w)

        def sgd(p, batch):
            g = jax.grad(loss)(p, *batch, precision)
            return jax.tree.map(
                lambda w, gw: (w.astype(jnp.float32)
                               - lr * gw.astype(jnp.float32)).astype(w.dtype),
                p, g), None

        return jax.lax.scan(sgd, p, batches)[0], None

    new = jax.lax.scan(one_pass, params, keys)[0]
    eff = jax.tree.map(lambda a, z: (a.astype(jnp.float32)
                                     - z.astype(jnp.float32)) / lr,
                       params, new)
    return new, eff


def accuracy(params, test_x, test_y, block: int, precision=HIGHEST):
    """Share of the test set the model labels right, ``block`` samples at
    a time (``block`` divides the test set)."""
    n = test_y.shape[0]
    xs = test_x.reshape(n // block, block, *test_x.shape[1:])
    ys = test_y.reshape(n // block, block)

    def step(hits, xy):
        pred = jnp.argmax(forward(params, xy[0], precision), -1)
        return hits + jnp.sum(pred == xy[1]), None

    hits = jax.lax.scan(step, jnp.int32(0), (xs, ys))[0]
    return hits.astype(jnp.float32) / n


def tree_sq_norm(t):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree.leaves(t))


# --------------------------------------------------------- arrival order ---

_M64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def _u01(seed: int, stream: int, client: int, k: int) -> float:
    h = _splitmix64(seed & _M64)
    for v in (stream, client, k):
        h = _splitmix64(h ^ (v & _M64))
    return ((h >> 11) + 0.5) * 2.0 ** -53


def first_arrivals(seed: int, n: int) -> np.ndarray:
    """Clients in the order of their first completions: the paper
    testbed's service time base_i * exp(0.15 z), z a Box-Muller normal from
    the counter hash (stream 1 = compute, draw 0); ties go to the lower
    client id."""
    base = np.full(n, 3.5)
    base[0] = 1.0
    if n > 1:
        base[1] = 4.5
    t = np.empty(n)
    for c in range(n):
        u1, u2 = _u01(seed, 1, c, 0), _u01(seed, 1, c, 1)
        z = math.sqrt(-2.0 * math.log(u1)) * math.cos(2.0 * math.pi * u2)
        t[c] = base[c] * math.exp(0.15 * z)
    return np.lexsort((np.arange(n), t))


def encode_seed(seed: int, step: int, client: int) -> int:
    """The upload's stochastic-rounding seed, a multiplicative mix of (run
    seed, upload kind 1, event position, client)."""
    h = (seed ^ 0x9E3779B9) & 0xFFFFFFFF
    h = (h * 1_000_003 + step) & 0xFFFFFFFF
    return (h * 1_000_003 + client) & 0xFFFFFFFF


# ----------------------------------------------------------------- codec ---

LANE, CHUNK, QMAX = 128, 256 * 128, 127.0


def hash_uniform(idx: np.ndarray, seed: int) -> np.ndarray:
    """Uniform [0, 1) from a uint32 index and seed (multiply-xorshift)."""
    x = idx.astype(np.uint32) * np.uint32(2654435761) + np.uint32(seed)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return (x >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


def topk_int8(flat: np.ndarray, frac: float, seed: int) -> np.ndarray:
    """What the server decodes from a top-k + int8 upload of ``flat``: the
    round(frac * n) largest magnitudes (ties at the threshold kept), each
    quantised stochastically to int8 on the scale max|x| / 127."""
    n = flat.size
    k = max(1, int(round(frac * n)))
    mag = np.abs(flat)
    kth = np.partition(mag, n - k)[n - k]
    thr = np.float32(max(kth, 1e-12))
    scale = np.float32(max(mag.max(), np.float32(1e-12)) / np.float32(QMAX))
    u = hash_uniform(np.arange(n, dtype=np.uint32), seed)
    y = np.clip(flat / scale, -QMAX, QMAX)
    q = np.clip(np.floor(y + u), -QMAX, QMAX)
    return np.where(mag >= thr, q * scale, 0.0).astype(np.float32)


def _flat(tree) -> np.ndarray:
    return np.concatenate([np.asarray(x, np.float32).ravel()
                           for x in jax.tree.leaves(tree)])


def _unflat(flat: np.ndarray, like):
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for x in leaves:
        out.append(flat[off:off + x.size].reshape(x.shape))
        off += x.size
    return jax.tree.unflatten(treedef, out)


# ----------------------------------------------------------- the window ---


def _blocks(f, args: tuple, block: int, shared: tuple = ()):
    """``f`` (vmapped over ``args``' leading axis) in blocks of ``block``
    rows, with ``shared`` passed whole to every block."""
    n = jax.tree.leaves(args)[0].shape[0]
    parts = [f(*jax.tree.map(lambda x: x[s:s + block], args), *shared)
             for s in range(0, n, block)]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)


@lru_cache(maxsize=8)
def _round_fn(local: tuple, precision):
    local = dict(local)
    return jax.jit(jax.vmap(lambda p, x, y, m, k: local_round(
        p, x, y, m, k, local, precision)))


@lru_cache(maxsize=8)
def _accuracy_fn(block: int, precision):
    return jax.jit(jax.vmap(lambda p, tx, ty: accuracy(
        p, tx, ty, block, precision), in_axes=(0, None, None)))


FAULTS = ("half_batch", "altered_update")

def control_kw(cfg: dict) -> dict:
    """How the control computes: the nearest precision below the
    configuration's, float32 at three bfloat16 passes (``high``) where the
    configuration states float32 at ``highest``, bfloat16 otherwise."""
    if cfg.get("matmul_precision") == "highest":
        if jax.default_backend() == "tpu":
            return {"precision": jax.lax.Precision.HIGH}
        return {"precision": THREE_PASS}
    return {"dtype": jnp.bfloat16, "precision": jax.lax.Precision.DEFAULT}


def updates(cfg: dict, traffic: dict, data: dict, seed: int, *,
            dtype=jnp.float32, precision=HIGHEST, fault=None) -> dict:
    """The device half of the federation's first engine window (all N
    clients complete once, from the seed): every client's local round from
    the initial model and, for VAFL, every client's Eq. 1 value.

    ``fault`` plants one of the benchmark's faults here, for reading what
    the comparison makes of it: ``half_batch`` leaves the second half of
    every mini-batch out of the loss (the mean taken over the rest);
    ``altered_update`` scales the first arrival's update by 1.1."""
    n = cfg["num_clients"]
    local = cfg["local"]
    key, init_key = jax.random.split(jax.random.key(seed))
    g0 = jax.tree.map(lambda x: x.astype(dtype),
                      cnn_init(cfg["model"], init_key))
    _, window_key = jax.random.split(key)
    order = first_arrivals(seed, n)
    position = np.empty(n, np.int64)
    position[order] = np.arange(n)
    keys = jax.random.split(window_key, n)[jnp.asarray(position)]
    mask = jnp.asarray(data["mask"])
    if fault == "half_batch":
        # a batch is a slice of a per-pass permutation, so the samples that
        # land in its second half are the ones dropped
        local = dict(local, half_batch=True)

    starts = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), g0)
    new, eff = _blocks(
        _round_fn(tuple(sorted(local.items())), precision),
        (starts, jnp.asarray(data["images"]), jnp.asarray(data["labels"]),
         mask, keys), cfg["reference"]["clients_per_step"])
    if fault == "altered_update":
        first = int(order[0])
        eff = jax.tree.map(lambda e: e.at[first].multiply(1.1), eff)
        new = jax.tree.map(lambda s0, e: (s0.astype(jnp.float32)
                                          - local["lr"] * e).astype(s0.dtype),
                           starts, eff)

    out = {"g0": g0, "order": order, "new": new,
           "eff": jax.tree.map(np.asarray, eff)}
    if traffic["algorithm"] == "vafl":
        test_x = jnp.asarray(data["test_images"])
        test_y = jnp.asarray(data["test_labels"])
        accs = _blocks(
            _accuracy_fn(cfg["reference"]["test_block"], precision), (new,),
            cfg["reference"]["eval_clients_per_step"], (test_x, test_y))
        amp = (1.0 + n / 1e3) ** accs
        out["values"] = np.asarray(jax.vmap(tree_sq_norm)(eff) * amp,
                                   np.float64)
    return out


def commits(cfg: dict, traffic: dict, upd: dict, seed: int, *,
            follow=None, tie_margin: float = 0.0) -> dict:
    """The host half of the first window: in arrival order, the gate, the
    compression of each upload with its error-feedback residual, and the
    FedBuff buffer flushes into the global model.

    ``follow`` maps a client to the other side's upload decision: where the
    reference's own value lies within ``tie_margin`` (relative) of the
    running mean, rounding decides the gate, and the reference takes the
    other side's decision there.  A decision that differs outside the
    margin is listed in ``flips``.  Returns ``upd``'s effective gradients
    and values with the decisions (client -> bool), the residuals of the
    uploads, the global model's change over the window, the margins and
    the flips."""
    n = cfg["num_clients"]
    algorithm = traffic["algorithm"]
    values = upd.get("values")
    codec = traffic["compressor"]
    frac = float(codec[4:].split("_")[0]) if codec != "identity" else None
    g0 = upd["g0"]
    g0_flat = _flat(g0)
    g = g0_flat.astype(np.float32)
    k_buf = cfg["buffer_size"]
    rho = 0.5
    buf, stale = [], []
    version = 0
    reported = np.full(n, np.inf)
    decisions, residuals, margins, flips = {}, {}, {}, []
    new_flat = None
    for j, i in enumerate(upd["order"]):
        i = int(i)
        if algorithm == "vafl":
            # the gate's mean runs over the finite values reported so far
            # (a client whose round diverged reports NaN); with none yet,
            # the report uploads
            reported[i] = values[i]
            finite = reported[np.isfinite(reported)]
            mean = finite.mean() if len(finite) else values[i]
            margin = (values[i] - mean) / mean if len(finite) else 0.0
            up = bool(values[i] >= mean) if len(finite) else True
            margins[i] = margin
            if follow is not None and follow.get(i, up) != up:
                if abs(margin) < tie_margin:
                    up = follow[i]
                else:
                    flips.append(i)
        else:
            up = True
        decisions[i] = up
        if not up:
            continue
        if new_flat is None:
            new_flat = np.concatenate(
                [np.asarray(x, np.float32).reshape(n, -1)
                 for x in jax.tree.leaves(upd["new"])], axis=1)
        if frac is None:
            recon = new_flat[i]
        else:
            delta = new_flat[i] - g0_flat
            decoded = topk_int8(delta, frac, encode_seed(seed, j, i))
            residuals[i] = delta - decoded
            recon = (g0_flat + decoded).astype(np.float32)
        buf.append(recon)
        stale.append(float(np.float32((1.0 + version) ** -0.5)))
        if len(buf) >= k_buf:
            s = np.asarray(stale, np.float64)
            if len(buf) == 1:
                r = np.float32(rho * s[0])
                g = ((1 - r) * g + r * buf[0]).astype(np.float32)
            else:
                coef = (s / s.sum()).astype(np.float32)
                r = np.float32(rho * s.mean())
                bar = np.einsum("k,kn->n", coef, np.stack(buf))
                g = ((1 - r) * g + r * bar).astype(np.float32)
            version += 1
            buf.clear()
            stale.clear()
    out = {"eff": upd["eff"], "decisions": decisions, "margins": margins,
           "flips": flips, "flushes": version,
           "residuals": {i: _unflat(r, g0) for i, r in residuals.items()},
           "global_delta": _unflat(g - g0_flat, g0)}
    if values is not None:
        out["values"] = values
    return out
