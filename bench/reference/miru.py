"""Plain reference of the M2RU paper's network and protocol.

Written from the paper (arXiv:2512.17299, eqs. 1-3, Algorithm 1, §IV-A,
§V-A) and the configuration files under ``bench/configs``; it imports
nothing of the program under test and takes nothing it made. Weights,
feedback matrix, batch order and replay draws are derived here from the
trainer seed, by the same recipe the paper's protocol states.

Substrate (``wbs``): every crossbar input is sign-magnitude quantized to
``input_bits`` (value code·2^-nb, code = round(|v|·(2^nb − 1))) and the
product is rescaled by 2^nb/(2^nb − 1); weights are read on the logical
scale ``weight_clip``; the hidden pre-activation passes the ADC
(``adc_bits`` over ±``adc_range``); writes are exact, clipped to
±``weight_clip``.

Precision lives in the products alone, named per kind of product by the
configuration's ``precision`` (``crossbar``, ``readout``, ``dfa``):
``highest`` (:func:`dot_f32`, float32 products), ``high``
(:func:`dot_bf16x3`, three bfloat16 passes) or ``default``
(:func:`dot_default`, XLA's DEFAULT: one bfloat16 pass with float32
accumulation on a TPU). :func:`control` lowers each stated precision by
one step, for the control that ``correct`` has to reject.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# The reservoir sampler's xorshift32 seed is the trainer seed XOR this
# constant (the replay unit's seed derivation).
SAMPLER_SEED_XOR = 0x5BD1E995


def dot_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    """A float32 matrix product (all six bf16 passes on a TPU)."""
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=F32)


def dot_bf16x3(a: jax.Array, b: jax.Array) -> jax.Array:
    """The three-pass bfloat16 product (``Precision.HIGH``), written out
    so that it rounds the same way on every backend: each operand splits
    into a bf16 head and a bf16 tail, and the tail×tail term is dropped."""
    def split(v):
        hi = v.astype(jnp.bfloat16).astype(F32)
        return hi, (v - hi).astype(jnp.bfloat16).astype(F32)

    ah, al = split(a)
    bh, bl = split(b)
    return dot_f32(ah, bh) + (dot_f32(ah, bl) + dot_f32(al, bh))


def dot_default(a: jax.Array, b: jax.Array) -> jax.Array:
    """A product at XLA's DEFAULT precision: on a TPU its operands are
    rounded to bfloat16 and the products summed in float32, one pass."""
    return jnp.dot(a, b, preferred_element_type=F32)


DOTS: dict[str, Callable] = {"highest": dot_f32, "high": dot_bf16x3,
                             "default": dot_default}
# One step below each precision: the step that would tempt a later
# change. ``default`` is the lowest float product of the v5e's MXU; a
# product stated at it stays there in the control.
STEP_DOWN = {"highest": "high", "high": "default", "default": "default"}


def control(precision: dict) -> dict:
    """The configuration's ``precision`` with every product one step
    lower."""
    return {k: STEP_DOWN[v] for k, v in precision.items()}


def _dots(precision: dict) -> dict[str, Callable]:
    return {k: DOTS[v] for k, v in precision.items()}


# ---------------------------------------------------------------------------
# Network
# ---------------------------------------------------------------------------

def _quantize_drive(v: jax.Array, n_bits: int) -> jax.Array:
    top = float(2 ** n_bits - 1)
    return jnp.clip(jnp.round(v * top), -top, top) * (2.0 ** -n_bits)


def _adc(v: jax.Array, bits: int, full_scale: float) -> jax.Array:
    levels = 2 ** bits
    step = 2.0 * full_scale / levels
    return jnp.clip(jnp.round(v / step), -(levels // 2),
                    levels // 2 - 1) * step


def crossbar(v: jax.Array, w: jax.Array, sub: dict, dot) -> jax.Array:
    """WBS crossbar product of drive ``v`` (M, K) with logical weights
    ``w`` (K, N)."""
    nb, c = sub["input_bits"], sub["weight_clip"]
    norm = 2.0 ** nb / (2.0 ** nb - 1.0)
    return dot(_quantize_drive(v, nb), w / c) * norm * c


def recurrence(p: dict, x: jax.Array, h0: jax.Array, net: dict, sub: dict,
               dots: dict) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Eqs. (1)-(2) over x (B, T, F) from h0 (B, H): returns h_t, h_{t-1}
    and the ADC'd pre-activation, each (B, T, H)."""
    B, T, F = x.shape
    H = p["u_h"].shape[0]
    beta, lam = net["beta"], net["lam"]
    dot = dots["crossbar"]
    drive = crossbar(x.reshape(B * T, F), p["w_h"], sub, dot)
    drive = drive.reshape(B, T, H)

    def step(h, d_t):
        pre = d_t + crossbar(beta * h, p["u_h"], sub, dot) + p["b_h"]
        pre = _adc(pre, sub["adc_bits"], sub["adc_range"])
        h_new = lam * h + (1.0 - lam) * jnp.tanh(pre)
        return h_new, (h_new, h, pre)

    _, outs = jax.lax.scan(step, h0, jnp.swapaxes(drive, 0, 1))
    return tuple(jnp.swapaxes(o, 0, 1) for o in outs)


def readout(p: dict, h: jax.Array, dots: dict) -> jax.Array:
    """Eq. (3)'s logits."""
    return dots["readout"](h, p["w_o"]) + p["b_o"]


# ---------------------------------------------------------------------------
# Initial state from the trainer seed
# ---------------------------------------------------------------------------

def _glorot(key, shape):
    limit = np.sqrt(6.0 / (shape[0] + shape[1]))
    return jax.random.uniform(key, shape, F32, -limit, limit)


def init_state(seed: int, net: dict) -> tuple[dict, jax.Array]:
    """Initial weights (Glorot matrices, zero biases) and the fixed DFA
    feedback Ψ ~ N(0, 1/n_y), from ``PRNGKey(seed)`` split into
    (carry, weights, Ψ)."""
    n_x, n_h, n_y = net["n_x"], net["n_h"], net["n_y"]
    key = jax.random.PRNGKey(seed)
    _, k_param, k_psi = jax.random.split(key, 3)
    k1, k2, k3 = jax.random.split(k_param, 3)
    params = {"w_h": _glorot(k1, (n_x, n_h)), "u_h": _glorot(k2, (n_h, n_h)),
              "b_h": jnp.zeros((n_h,), F32), "w_o": _glorot(k3, (n_h, n_y)),
              "b_o": jnp.zeros((n_y,), F32)}
    std = float(np.float32(1.0) / np.sqrt(np.float32(n_y)))
    psi = std * jax.random.normal(k_psi, (n_y, n_h), F32)
    return params, psi


# ---------------------------------------------------------------------------
# The replay-mixed batch stream (§IV-A)
# ---------------------------------------------------------------------------

class _Xorshift32:
    def __init__(self, seed: int):
        self.state = (seed & 0xFFFFFFFF) or 0xDEADBEEF

    def next(self) -> int:
        x = self.state
        x ^= (x << 13) & 0xFFFFFFFF
        x ^= x >> 17
        x ^= (x << 5) & 0xFFFFFFFF
        self.state = x
        return x


@functools.partial(jax.jit, static_argnums=2)
def _quantize_rows(rows: jax.Array, key: jax.Array, n_bits: int):
    """Stochastic rounding of every stored row, each with the next key of
    one sequential ``key, sub = split(key)`` chain."""
    def chain(k, _):
        k, sub = jax.random.split(k)
        return k, sub

    _, subs = jax.lax.scan(chain, key, None, length=rows.shape[0])

    def one(x, k):
        z = x * (2.0 ** n_bits)
        fl = jnp.floor(z)
        r = jax.random.uniform(k, x.shape)
        top = 2.0 ** n_bits - 1.0
        q = jnp.where((r < z - fl) & (fl < top), fl + 1.0, fl)
        return jnp.clip(q, 0.0, top)

    return jax.vmap(one)(rows, subs) / (2.0 ** n_bits)


def batch_stream(seed: int, x_train: list, y_train: list, tr: dict,
                 rp: dict) -> tuple[np.ndarray, np.ndarray]:
    """Every training batch of the protocol: per task and epoch a
    shuffle (``default_rng(seed + 1)``), batches of ``batch_size``; from
    the second task on, the last ``round(ratio·B)`` rows come from the
    reservoir (algorithm R with the xorshift32 modulus unit), stored as
    ``bits``-bit stochastic codes. Returns xs (n_tasks, S, B, T, F) and
    ys (n_tasks, S, B)."""
    bs, cap = tr["batch_size"], rp["capacity"]
    rng = np.random.default_rng(seed + 1)
    sampler = _Xorshift32(seed ^ SAMPLER_SEED_XOR)
    count = 0
    slot_write = np.full(cap, -1, np.int64)
    writes: list[tuple[int, int]] = []
    plan = []                            # (task, fresh rows, replay writes)
    for t, xt in enumerate(x_train):
        n = xt.shape[0]
        for _ in range(tr["epochs_per_task"]):
            order = rng.permutation(n)
            for s in range(0, n - bs + 1, bs):
                idx = order[s:s + bs]
                n_rep, rep = 0, np.zeros(0, np.int64)
                occupancy = min(count, cap)
                if t > 0 and occupancy > 0 and rp["ratio"] > 0:
                    n_rep = int(round(bs * rp["ratio"]))
                    if n_rep > 0:
                        rep = slot_write[rng.integers(0, occupancy,
                                                      size=n_rep)]
                fresh = idx[:bs - n_rep]
                for i in fresh:
                    count += 1
                    if count <= cap:
                        slot = count - 1
                    else:
                        j = 1 + sampler.next() % count
                        slot = j - 1 if j <= cap else None
                    if slot is not None:
                        slot_write[slot] = len(writes)
                        writes.append((t, int(i)))
                plan.append((t, fresh, rep))
    rows = np.stack([x_train[t][i] for t, i in writes])
    stored = np.asarray(_quantize_rows(jnp.asarray(rows),
                                       jax.random.PRNGKey(seed), rp["bits"]))
    labels = np.asarray([y_train[t][i] for t, i in writes], np.int32)
    xs, ys = [], []
    for t, fresh, rep in plan:
        xs.append(np.concatenate([x_train[t][fresh], stored[rep]]))
        ys.append(np.concatenate([y_train[t][fresh], labels[rep]]))
    n_tasks = len(x_train)
    xs = np.stack(xs).astype(np.float32)
    ys = np.stack(ys).astype(np.int32)
    return (xs.reshape(n_tasks, -1, *xs.shape[1:]),
            ys.reshape(n_tasks, -1, bs))


# ---------------------------------------------------------------------------
# Training: DFA through time (Algorithm 1) and the continual protocol
# ---------------------------------------------------------------------------

def _sparsify(g: jax.Array, keep_frac: float) -> jax.Array:
    """ζ: keep the round(keep_frac·n) entries of largest magnitude (ties
    to the earlier index), zero the rest."""
    flat = g.reshape(-1)
    k = max(1, int(round(keep_frac * flat.size)))
    if k >= flat.size:
        return g
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    keep = jnp.zeros(flat.shape, bool).at[idx].set(True)
    return jnp.where(keep, flat, 0.0).reshape(g.shape)


def dfa_step(p: dict, psi: jax.Array, x: jax.Array, y: jax.Array,
             net: dict, sub: dict, tr: dict, dots: dict):
    """One DFA step: returns (new params, loss)."""
    B, T, F = x.shape
    H, n_y = psi.shape[1], psi.shape[0]
    h_all, h_prev, pre = recurrence(p, x, jnp.zeros((B, H), F32), net,
                                    sub, dots)
    h_T = h_all[:, -1]
    logits = readout(p, h_T, dots)
    dot = dots["dfa"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    loss = jnp.mean(logz - jnp.take_along_axis(logits, y[:, None], 1)[:, 0])
    delta_o = (jax.nn.softmax(logits, -1) - jax.nn.one_hot(y, n_y)) / B
    e = dot(delta_o, psi) / T
    delta_h = net["lam"] * e[:, None, :] * (1.0 - jnp.tanh(pre) ** 2)
    dh = delta_h.reshape(B * T, H)
    grads = {"w_h": dot(x.reshape(B * T, F).T, dh),
             "u_h": dot((net["beta"] * h_prev).reshape(B * T, H).T, dh),
             "b_h": jnp.sum(delta_h, axis=(0, 1)),
             "w_o": dot(h_T.T, delta_o),
             "b_o": jnp.sum(delta_o, axis=0)}
    new = {}
    for name, g in grads.items():
        if g.ndim >= 2:
            g = _sparsify(g, tr["kwta_keep_frac"])
        lr = tr["lr"] * (tr["hidden_lr_scale"]
                         if name in ("w_h", "u_h", "b_h") else 1.0)
        new[name] = jnp.clip(p[name] - lr * g, -sub["weight_clip"],
                             sub["weight_clip"])
    return new, loss


def accuracy(p: dict, x: jax.Array, y: jax.Array, net: dict, sub: dict,
             dots: dict) -> jax.Array:
    B, _, _ = x.shape
    H = p["u_h"].shape[0]
    h_all, _, _ = recurrence(p, x, jnp.zeros((B, H), F32), net, sub, dots)
    logits = readout(p, h_all[:, -1], dots)
    return jnp.mean((jnp.argmax(logits, -1) == y).astype(F32))


def protocol(params, psi, xs, ys, test_x, test_y, *, net, sub, tr, dots):
    """The continual protocol: per task a scan over its batches, then an
    accuracy on every task's test set. Returns per-step losses
    (n_tasks, S), R_full (n_tasks, n_tasks), the untrained baseline row,
    and the final weights."""
    def eval_all(p):
        return jax.lax.map(
            lambda xy: accuracy(p, xy[0], xy[1], net, sub, dots),
            (test_x, test_y))

    def task(p, xy):
        def step(p, b):
            p, loss = dfa_step(p, psi, b[0], b[1], net, sub, tr, dots)
            return p, loss

        p, losses = jax.lax.scan(step, p, xy)
        return p, (losses, eval_all(p))

    base = eval_all(params)
    final, (losses, R_full) = jax.lax.scan(task, params, (xs, ys))
    return losses, R_full, base, final


@functools.lru_cache(maxsize=None)
def protocol_fn(net_items: tuple, sub_items: tuple, tr_items: tuple,
                precision_items: tuple):
    """The jitted protocol, vmapped over seeds, for one configuration and
    one precision."""
    kw = dict(net=dict(net_items), sub=dict(sub_items), tr=dict(tr_items),
              dots=_dots(dict(precision_items)))
    fn = functools.partial(protocol, **kw)
    return jax.jit(jax.vmap(fn, in_axes=(0, 0, 0, 0, None, None)))


def run_protocol(seeds: list[int], x_train, y_train, x_test, y_test,
                 net: dict, sub: dict, tr: dict, rp: dict,
                 precision: dict, rows: int = None) -> dict:
    """The whole protocol for each seed, on the device, at the timed
    sizes, with products at ``precision`` (the configuration's, or
    :func:`control`'s). Returns numpy arrays with a leading seed axis.
    ``rows`` keeps
    only the first rows of every batch (a fault for the check's tests:
    half of each batch left out)."""
    init = [init_state(s, net) for s in seeds]
    streams = [batch_stream(s, x_train, y_train, tr, rp) for s in seeds]
    if rows is not None:
        streams = [(x[:, :, :rows], y[:, :, :rows]) for x, y in streams]
    fn = protocol_fn(tuple(sorted(net.items())), tuple(sorted(sub.items())),
                     tuple(sorted(tr.items())),
                     tuple(sorted(precision.items())))
    stack = lambda *a: jnp.stack(a)  # noqa: E731
    params = jax.tree.map(stack, *[p for p, _ in init])
    psi = jnp.stack([q for _, q in init])
    losses, R_full, base, final = fn(
        params, psi, jnp.asarray(np.stack([s[0] for s in streams])),
        jnp.asarray(np.stack([s[1] for s in streams])),
        jnp.asarray(np.stack(x_test)), jnp.asarray(np.stack(y_test)))
    init_np = jax.tree.map(np.asarray, params)
    return {"losses": np.asarray(losses), "R_full": np.asarray(R_full),
            "baseline_row": np.asarray(base),
            "params": jax.tree.map(np.asarray, final), "init": init_np}


# ---------------------------------------------------------------------------
# Serving: per-frame readout of a user's stream, state carried across
# that user's requests
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def stream_fn(net_items: tuple, sub_items: tuple, precision_items: tuple):
    net, sub = dict(net_items), dict(sub_items)
    dots = _dots(dict(precision_items))

    def chunk(p, h, x):
        h_all, _, _ = recurrence(p, x, h, net, sub, dots)
        B, C, H = h_all.shape
        logits = readout(p, h_all.reshape(B * C, H), dots)
        return h_all[:, -1], logits.reshape(B, C, -1)

    return jax.jit(chunk)


def stream_logits(params: dict, frames: np.ndarray, net: dict, sub: dict,
                  precision: dict, chunk: int = 1024) -> np.ndarray:
    """Logits of every frame of ``frames`` (B, L, F), each row one user's
    requests back to back from a zero state. Runs in fixed chunks of
    ``chunk`` frames (one compiled program whatever L is); frames past a
    row's end are zero and their logits are never read."""
    B, L, F = frames.shape
    Lp = -(-L // chunk) * chunk
    x = np.zeros((B, Lp, F), np.float32)
    x[:, :L] = frames
    fn = stream_fn(tuple(sorted(net.items())), tuple(sorted(sub.items())),
                   tuple(sorted(precision.items())))
    p = jax.tree.map(jnp.asarray, params)
    h = jnp.zeros((B, params["u_h"].shape[0]), F32)
    out = []
    for s in range(0, Lp, chunk):
        h, lg = fn(p, h, jnp.asarray(x[:, s:s + chunk]))
        out.append(np.asarray(lg))
    return np.concatenate(out, axis=1)[:, :L]
