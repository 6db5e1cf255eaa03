"""Plain reference of a heterogeneous fleet of M2RU chips on the
conductance-domain substrate.

Written from the paper (arXiv:2512.17299, §IV-B-1: a weight is the
scaled conductance difference of a device pair; §V-B: R_on 2 MΩ, R_off
20 MΩ, write and cycle-to-cycle read variation) and the configuration
files under ``bench/configs``. It imports nothing of the program under
test and takes nothing it made; the network, the DFA step's products,
the initial state and the replay-mixed batch stream are those of
``bench/reference/miru.py``. Every random draw is derived here from the
fleet seed, by the recipe the program states:

* Per-chip seeds. Successive words of one Xorshift32 (13/17/5) chain
  whose state starts at ``fleet_seed XOR 0xF1EE7D0C`` (32 bits); chip
  ``d`` trains on the stream and initial state of trainer seed ``d``'s
  word (``miru.init_state``, ``miru.batch_stream``).
* Per-chip knobs. ``base = fold_in(PRNGKey(fleet_seed), 0x48E7)``; knob
  ``i`` in sorted name order (drift_rate, prog_sigma, read_sigma,
  write_sigma) draws ``z = normal(fold_in(base, i), (n_chips,))`` and
  takes ``mean · exp(spread·z − spread²/2)`` (lognormal, mean-
  preserving), with (mean, spread) from the configuration's ``fleet``.
* Programming. A logical weight w ∈ [−w_clip, w_clip] maps to a pair:
  G⁺ = G_off + max(w/w_clip, 0)·(G_on − G_off), G⁻ the same for −w, each
  then multiplied by (1 + prog_sigma·N(0, 1)) and clipped to
  [G_off, G_on]. Chip key ``fold_in(PRNGKey(chip seed), 0xF1EE7)``
  splits into one key per matrix (u_h, w_h, w_o, sorted), each split
  into the G⁺ and G⁻ draws.
* Training keys. ``PRNGKey(chip seed)`` splits 3-way (carry, weights,
  Ψ); the carry walks a ``key, sub = split(key)`` chain: per task one
  sub per step, then one for the task's evaluations (the untrained
  baseline uses task 0's). A step's sub splits into (forward, write).
* A read (per access, §V-B cycle-to-cycle variation). The forward key
  walks ``k, k1, k2 = split(k, 3)`` per time step, k1 for W_h and k2 for
  U_h. An access key splits into (G⁺ noise, G⁻ noise, plane gains): both
  devices are multiplied by (1 + read_sigma·N(0, 1)), the weight read is
  (G⁺ − G⁻)·w_clip/(G_on − G_off). The drive is sign-magnitude
  quantized to ``input_bits``; bit plane b (MSB first) is weighted by the
  memristor-ratio gain 2^−(b+1)·(1 + gain_sigma·N(0, 1)) (one draw per
  plane), and the product is rescaled by 2^nb/(2^nb − 1). The hidden
  pre-activation passes the ADC (``adc_bits`` over ±``adc_range``).
  The readout (eq. 3) is digital, on the logical weights.
* A write (per update). The write key splits into one key per
  parameter (b_h, b_o, u_h, w_h, w_o, sorted). Each pair first relaxes
  one retention tick toward G_off (G ← G_off + (G − G_off)·(1 −
  drift_rate)); a nonzero logical delta then lands on G⁺ (positive) or
  G⁻ (negative) as |dw|/w_clip·(G_on − G_off)·(1 + write_sigma·N(0, 1)),
  clipped to the window, and the logical weight becomes the pair's
  noiseless read-back. Biases are digital registers: their key splits
  once more, and a nonzero delta lands as dw·(1 + σ·N(0, 1)) with the
  substrate's static write σ, clipped to ±``weight_clip``.

Precision is the configuration's, as in ``miru``: crossbar products at
``highest`` under ``jax.default_matmul_precision("highest")``, the
readout and DFA products as stated; ``miru.control`` lowers each one
step for the control ``correct`` has to reject. ``het=None`` puts every
chip at the profile's means (the fault that shows the per-chip draws
matter).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import miru

F32 = jnp.float32
SEED_SALT = 0xF1EE7D0C
HET_FOLD = 0x48E7
PROG_FOLD = 0xF1EE7
CROSSBAR = ("u_h", "w_h", "w_o")


# ---------------------------------------------------------------------------
# The population, from the fleet seed
# ---------------------------------------------------------------------------

def chip_seeds(fleet_seed: int, n: int) -> list[int]:
    rng = miru._Xorshift32((fleet_seed ^ SEED_SALT) & 0xFFFFFFFF)
    return [rng.next() for _ in range(n)]


def knob_draws(fleet_seed: int, n: int, profile: dict) -> dict:
    """Each chip's knobs, (n,) float32 per name."""
    base = jax.random.fold_in(jax.random.PRNGKey(fleet_seed), HET_FOLD)
    out = {}
    for i, name in enumerate(sorted(profile)):
        mean, spread = profile[name]
        z = jax.random.normal(jax.random.fold_in(base, i), (n,))
        out[name] = np.asarray(
            (mean * jnp.exp(spread * z - 0.5 * spread * spread))
            .astype(F32))
    return out


def _window(cb: dict) -> tuple[float, float]:
    """(G_off, G_on) of the device window."""
    return 1.0 / cb["r_off"], 1.0 / cb["r_on"]


def program_pairs(params: dict, seed: int, prog_sigma, cb: dict) -> dict:
    g_off, g_on = _window(cb)
    g_range = g_on - g_off
    keys = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), PROG_FOLD),
        len(CROSSBAR))
    pairs = {}
    for k, name in zip(keys, CROSSBAR):
        wn = jnp.clip(params[name] / cb["w_clip"], -1.0, 1.0)
        kp, kn = jax.random.split(k)
        gp = (g_off + jnp.maximum(wn, 0.0) * g_range) \
            * (1.0 + prog_sigma * jax.random.normal(kp, wn.shape))
        gn = (g_off + jnp.maximum(-wn, 0.0) * g_range) \
            * (1.0 + prog_sigma * jax.random.normal(kn, wn.shape))
        pairs[name] = {"g_pos": jnp.clip(gp, g_off, g_on),
                       "g_neg": jnp.clip(gn, g_off, g_on)}
    return pairs


# ---------------------------------------------------------------------------
# Read and write through the pairs
# ---------------------------------------------------------------------------

def _read_back(pair: dict, cb: dict) -> jax.Array:
    g_off, g_on = _window(cb)
    return (pair["g_pos"] - pair["g_neg"]) * (cb["w_clip"] / (g_on - g_off))


def _access(v, pair, key, knobs, sub, dot):
    """One crossbar access: the drive ``v`` (M, K) through a fresh noisy
    read of ``pair``."""
    cb = sub["crossbar"]
    kp, kn, kg = jax.random.split(key, 3)
    sigma = knobs["read_sigma"]
    noisy = {"g_pos": pair["g_pos"] * (1.0 + sigma * jax.random.normal(
                 kp, pair["g_pos"].shape)),
             "g_neg": pair["g_neg"] * (1.0 + sigma * jax.random.normal(
                 kn, pair["g_neg"].shape))}
    w = _read_back(noisy, cb)
    nb, c = sub["input_bits"], sub["weight_clip"]
    gains = 2.0 ** -jnp.arange(1, nb + 1, dtype=F32)
    if sub["gain_sigma"] > 0:
        gains = gains * (1.0 + sub["gain_sigma"]
                         * jax.random.normal(kg, gains.shape))
    top = 2 ** nb - 1
    code = jnp.clip(jnp.round(jnp.abs(v) * top), 0, top).astype(jnp.int32)
    planes = [((code >> (nb - 1 - b)) & 1).astype(F32) for b in range(nb)]
    drive = jnp.sign(v) * sum(g * p for g, p in zip(gains, planes))
    with jax.default_matmul_precision("highest"):
        y = dot(drive, w / c)
    return y * (2.0 ** nb / (2.0 ** nb - 1.0)) * c


def forward(p, pairs, x, key, knobs, net, sub, dots):
    """Eqs. (1)-(3) with every crossbar access read through the pairs:
    logits, and h_t, h_{t-1}, pre-activations, each (B, T, H)."""
    B = x.shape[0]
    H = p["u_h"].shape[0]
    beta, lam = net["beta"], net["lam"]
    dot = dots["crossbar"]

    def step(carry, x_t):
        h, k = carry
        k, k1, k2 = jax.random.split(k, 3)
        pre = _access(x_t, pairs["w_h"], k1, knobs, sub, dot) \
            + _access(beta * h, pairs["u_h"], k2, knobs, sub, dot) \
            + p["b_h"]
        pre = miru._adc(pre, sub["adc_bits"], sub["adc_range"])
        h_new = lam * h + (1.0 - lam) * jnp.tanh(pre)
        return (h_new, k), (h_new, h, pre)

    _, outs = jax.lax.scan(step, (jnp.zeros((B, H), F32), key),
                           jnp.swapaxes(x, 0, 1))
    h_all, h_prev, pre = (jnp.swapaxes(o, 0, 1) for o in outs)
    return miru.readout(p, h_all[:, -1], dots), h_all, h_prev, pre


def write(p, pairs, dw, key, knobs, sub):
    """Drift one tick, land the deltas, read the logical weights back."""
    cb = sub["crossbar"]
    g_off, g_on = _window(cb)
    keep = 1.0 - knobs["drift_rate"]
    new_p, new_pairs = {}, {}
    for kw, name in zip(jax.random.split(key, len(p)), sorted(p)):
        d = dw[name]
        if name in pairs:
            pair = {k: g_off + (g - g_off) * keep
                    for k, g in pairs[name].items()}
            dg = jnp.abs(d) / cb["w_clip"] * (g_on - g_off) * (
                1.0 + knobs["write_sigma"] * jax.random.normal(kw, d.shape))
            gp = jnp.where(d > 0, pair["g_pos"] + dg, pair["g_pos"])
            gn = jnp.where(d < 0, pair["g_neg"] + dg, pair["g_neg"])
            pair = {"g_pos": jnp.clip(gp, g_off, g_on),
                    "g_neg": jnp.clip(gn, g_off, g_on)}
            new_pairs[name] = pair
            new_p[name] = _read_back(pair, cb)
        else:
            noise = 1.0 + cb["write_sigma"] * jax.random.normal(
                jax.random.split(kw, 1)[0], d.shape)
            landed = jnp.where(d != 0, d * noise, 0.0)
            new_p[name] = jnp.clip(p[name] + landed, -sub["weight_clip"],
                                   sub["weight_clip"])
    return new_p, new_pairs


def dfa_updates(p, psi, x, y, outs, net, tr, dots):
    """Algorithm 1's ζ-sparsified, scaled deltas and the step's loss."""
    logits, h_all, h_prev, pre = outs
    B, T, F = x.shape
    H, n_y = psi.shape[1], psi.shape[0]
    dot = dots["dfa"]
    h_T = h_all[:, -1]
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
    dw = {}
    for name, g in grads.items():
        if g.ndim >= 2:
            g = miru._sparsify(g, tr["kwta_keep_frac"])
        scale = tr["hidden_lr_scale"] if name in ("w_h", "u_h", "b_h") \
            else 1.0
        dw[name] = (-tr["lr"] * scale) * g
    return dw, loss


# ---------------------------------------------------------------------------
# The protocol, per chip
# ---------------------------------------------------------------------------

def protocol(params, psi, pairs, knobs, key, xs, ys, test_x, test_y, *,
             net, sub, tr, dots):
    """One chip's continual protocol: per-step losses (n_tasks, S),
    R_full, the untrained baseline row and the final logical weights."""
    n_tasks, S = xs.shape[:2]

    def chain(k, _):
        k, s = jax.random.split(k)
        return k, s

    _, subs = jax.lax.scan(chain, key, None, length=n_tasks * (S + 1))
    subs = subs.reshape(n_tasks, S + 1, 2)
    step_keys, eval_keys = subs[:, :S], subs[:, S]

    def eval_all(p, prs, k):
        def one(xy):
            logits = forward(p, prs, xy[0], k, knobs, net, sub, dots)[0]
            return jnp.mean((jnp.argmax(logits, -1) == xy[1]).astype(F32))
        return jax.lax.map(one, (test_x, test_y))

    def task(carry, inp):
        x_t, y_t, k_t, k_eval = inp

        def step(c, b):
            p, prs = c
            x, y, k = b
            k_fwd, k_wr = jax.random.split(k)
            outs = forward(p, prs, x, k_fwd, knobs, net, sub, dots)
            dw, loss = dfa_updates(p, psi, x, y, outs, net, tr, dots)
            return write(p, prs, dw, k_wr, knobs, sub), loss

        (p, prs), losses = jax.lax.scan(step, carry, (x_t, y_t, k_t))
        return (p, prs), (losses, eval_all(p, prs, k_eval))

    base = eval_all(params, pairs, eval_keys[0])
    (final, _), (losses, R_full) = jax.lax.scan(
        task, (params, pairs), (xs, ys, step_keys, eval_keys))
    return losses, R_full, base, final


def _frozen(d: dict) -> tuple:
    return tuple(sorted((k, _frozen(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


def _thawed(t: tuple) -> dict:
    return {k: _thawed(v) if isinstance(v, tuple) and v
            and isinstance(v[0], tuple) else v for k, v in t}


@functools.lru_cache(maxsize=None)
def protocol_fn(net_items: tuple, sub_items: tuple, tr_items: tuple,
                precision_items: tuple):
    """The jitted protocol, vmapped over chips, for one configuration
    and one precision."""
    kw = dict(net=_thawed(net_items), sub=_thawed(sub_items),
              tr=_thawed(tr_items), dots=miru._dots(dict(precision_items)))
    fn = functools.partial(protocol, **kw)
    return jax.jit(jax.vmap(fn, in_axes=(0,) * 7 + (None, None)))


def run_fleet_protocol(fleet_seed: int, chips: list[int], n_chips: int,
                       x_train, y_train, x_test, y_test, net: dict,
                       sub: dict, tr: dict, rp: dict, fleet: dict,
                       precision: dict, het: str = "drawn",
                       rows: int = None) -> dict:
    """The protocol of chips ``chips`` (indices into a fleet of
    ``n_chips`` drawn from ``fleet_seed``), on the device, at the timed
    sizes, with products at ``precision``. ``het=None`` puts every chip
    at the profile's means; ``rows`` keeps only the first rows of every
    batch (a fault for the check's tests). Returns numpy arrays with a
    leading chip axis, and the chips' seeds."""
    seeds = chip_seeds(fleet_seed, n_chips)
    profile = fleet["profile"]
    if het is None:
        knobs = {k: np.full(n_chips, m, np.float32)
                 for k, (m, _) in profile.items()}
    else:
        knobs = knob_draws(fleet_seed, n_chips, profile)
    picked = [seeds[d] for d in chips]
    knobs = {k: jnp.asarray(v[list(chips)]) for k, v in knobs.items()}
    init = [miru.init_state(s, net) for s in picked]
    params = jax.tree.map(lambda *a: jnp.stack(a), *[p for p, _ in init])
    psi = jnp.stack([q for _, q in init])
    pairs = jax.tree.map(lambda *a: jnp.stack(a), *[
        program_pairs(jax.tree.map(lambda v: v[i], params), s,
                      knobs["prog_sigma"][i], sub["crossbar"])
        for i, s in enumerate(picked)])
    keys = jnp.stack([jax.random.split(jax.random.PRNGKey(s), 3)[0]
                      for s in picked])
    streams = [miru.batch_stream(s, x_train, y_train, tr, rp)
               for s in picked]
    if rows is not None:
        streams = [(x[:, :, :rows], y[:, :, :rows]) for x, y in streams]
    fn = protocol_fn(_frozen(net), _frozen(sub), _frozen(tr),
                     _frozen(precision))
    losses, R_full, base, final = fn(
        params, psi, pairs, knobs, keys,
        jnp.asarray(np.stack([s[0] for s in streams])),
        jnp.asarray(np.stack([s[1] for s in streams])),
        jnp.asarray(np.stack(x_test)), jnp.asarray(np.stack(y_test)))
    return {"losses": np.asarray(losses), "R_full": np.asarray(R_full),
            "baseline_row": np.asarray(base),
            "params": jax.tree.map(np.asarray, final),
            "init": jax.tree.map(np.asarray, params), "seeds": picked}
