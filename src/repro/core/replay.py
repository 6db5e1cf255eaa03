"""Hardware experience-replay pipeline (§IV-A): reservoir sampler,
stochastic quantizer, replay buffer.

The paper's data-preparation unit is digital host-side logic (counter,
xorshift32, modulus unit, LFSR-driven stochastic rounder). It is reproduced
here bit-faithfully in numpy for the host path, plus vectorized jnp versions
of the quantizers for the in-graph replay path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracer import span


# ---------------------------------------------------------------------------
# Xorshift32 — the paper's RNG (chosen over LFSR for unbiased indices)
# ---------------------------------------------------------------------------

class Xorshift32:
    """32-bit xorshift PRNG (Marsaglia), exactly the 13/17/5 hardware circuit.

    Produces decorrelated, uniform *words* — the property the paper relies
    on for equal-probability reservoir sampling (unlike an LFSR, whose
    maximal sequence never emits 0 and is correlated between taps).

    ``randint`` reduces a word to a range. The hardware-faithful default
    (``mode="modulus"``, the paper's modulus unit) carries modulo bias
    when the span does not divide 2^32: each value's probability deviates
    from 1/span by at most 2^-32 in absolute terms, but residues below
    ``2^32 mod span`` are overweighted by the factor
    ``ceil(2^32/span)/floor(2^32/span)`` — approaching 2× for spans near
    2^32 (quantified in tests/test_replay.py). ``mode="reject"`` draws
    words until one falls below the largest multiple of the span — exactly
    uniform, at the cost of a variable number of RNG steps, so it walks a
    *different* bit-stream and must not be enabled under seeds that
    hardware-equivalence tests pin.
    """

    def __init__(self, seed: int = 0x9E3779B9, mode: str = "modulus"):
        if mode not in ("modulus", "reject"):
            raise ValueError(f"unknown randint mode {mode!r}; expected "
                             "'modulus' (hardware-faithful) or 'reject' "
                             "(unbiased)")
        seed = np.uint32(seed if seed != 0 else 0xDEADBEEF)
        self.state = np.uint32(seed)
        self.mode = mode

    def next(self) -> int:
        x = self.state
        with np.errstate(over="ignore"):
            x = np.uint32(x ^ np.uint32(x << np.uint32(13)))
            x = np.uint32(x ^ np.uint32(x >> np.uint32(17)))
            x = np.uint32(x ^ np.uint32(x << np.uint32(5)))
        self.state = x
        return int(x)

    def randint(self, lo: int, hi: int) -> int:
        """Int in [lo, hi]: the paper's modulus unit by default (modulo
        bias ≤ 2^-32 per value — see the class docstring), or unbiased
        rejection sampling when constructed with ``mode="reject"``."""
        span = hi - lo + 1
        if self.mode == "reject":
            limit = (1 << 32) - ((1 << 32) % span)
            x = self.next()
            while x >= limit:
                x = self.next()
            return lo + x % span
        return lo + self.next() % span


# ---------------------------------------------------------------------------
# Stochastic quantizer (eqs. 4-6)
# ---------------------------------------------------------------------------

def stochastic_quantize(x: jax.Array, key: jax.Array, n_bits: int
                        ) -> jax.Array:
    """Quantize x∈[0,1] to n_bits integer codes with stochastic rounding.

        z  = x · 2^{n_b}
        q  = ⌊z⌋ + 1   if r < frac(z) and ⌊z⌋ < 2^{n_b} − 1
             ⌊z⌋       otherwise,   r ~ U(0,1)

    Unbiased away from the top code: for x ≤ 1 − 2^{−n_b},
    E[dequantize(q)] == x exactly. Codes saturate at 2^{n_b} − 1 while
    :func:`dequantize` divides by 2^{n_b} (the hardware's n-bit right
    shift), so inputs in the clip region (1 − 2^{−n_b}, 1] come back
    pinned at 1 − 2^{−n_b} — a stored 1.0 pixel is always replayed one
    LSB dim. :func:`round_trip_bound` exposes the worst-case error.
    """
    z = x * (2.0 ** n_bits)
    fl = jnp.floor(z)
    frac = z - fl
    r = jax.random.uniform(key, x.shape)
    top = 2.0 ** n_bits - 1.0
    q = jnp.where((r < frac) & (fl < top), fl + 1.0, fl)
    return jnp.clip(q, 0.0, top).astype(jnp.uint8 if n_bits <= 8
                                        else jnp.uint16)


def uniform_quantize(x: jax.Array, n_bits: int) -> jax.Array:
    """Plain truncation quantizer (the baseline in Fig. 5a)."""
    z = jnp.floor(x * (2.0 ** n_bits))
    top = 2.0 ** n_bits - 1.0
    return jnp.clip(z, 0.0, top).astype(jnp.uint8 if n_bits <= 8
                                        else jnp.uint16)


def dequantize(q: jax.Array, n_bits: int, dtype=jnp.float32) -> jax.Array:
    """Codes → [0, 1): the paper-faithful 1/2^{n_b} scale (an n-bit right
    shift in RTL). Because codes saturate at 2^{n_b} − 1, the top of the
    dequantized range is 1 − 2^{−n_b}, not 1.0 — see
    :func:`round_trip_bound`."""
    return q.astype(dtype) / (2.0 ** n_bits)


def round_trip_bound(n_bits: int) -> float:
    """Worst-case |E[dequantize(stochastic_quantize(x))] − x| over
    x ∈ [0, 1].

    The stochastic rounder is exactly unbiased on x ≤ 1 − 2^{−n_b}; in
    the clip region (1 − 2^{−n_b}, 1] the expectation is pinned at
    1 − 2^{−n_b}, so the error grows linearly to its maximum 2^{−n_b}
    at x = 1.0. Scaling dequantization by 1/(2^{n_b} − 1) instead would
    remove the clip but is *not* what the chip's shift-based datapath
    computes — the repro keeps the paper-faithful scale and documents
    the bound (pinned by a property test in tests/test_replay.py).
    """
    return 2.0 ** -n_bits


def code_dtype(n_bits: int) -> np.dtype:
    """Storage dtype for n_bits codes: uint8 holds up to 8-bit codes,
    uint16 up to 16 — matching what the quantizers emit. (Allocating
    uint8 unconditionally silently truncated the high bits of 9–16-bit
    codes.)"""
    if not 1 <= n_bits <= 16:
        raise ValueError(f"n_bits must be in [1, 16], got {n_bits}")
    return np.dtype(np.uint8 if n_bits <= 8 else np.uint16)


def lfsr_stochastic_quantize(x: np.ndarray, n_bits: int, seed: int = 1
                             ) -> np.ndarray:
    """Bit-faithful hardware rounder: an n_bits LFSR supplies r (Verilog
    model in §IV-A-2). Host-side numpy; used in hardware-equivalence tests."""
    taps = {4: (3, 2), 8: (7, 5, 4, 3)}[n_bits if n_bits in (4, 8) else 4]
    state = seed & ((1 << n_bits) - 1) or 1
    flat = x.reshape(-1)
    out = np.empty_like(flat)
    top = 2 ** n_bits - 1
    for i, v in enumerate(flat):
        fb = 0
        for t in taps:
            fb ^= (state >> t) & 1
        state = ((state << 1) | fb) & ((1 << n_bits) - 1)
        z = v * (2.0 ** n_bits)
        fl = np.floor(z)
        r = state / (2.0 ** n_bits)
        q = fl + 1 if (r < (z - fl) and fl < top) else fl
        out[i] = min(max(q, 0), top)
    return out.reshape(x.shape)


@functools.partial(jax.jit, static_argnums=1)
def _split_chain(key: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """n sequential ``key, sub = split(key)`` steps in one dispatch.
    Returns (advanced key, (n, 2) subkeys) — bit-identical to the loop."""
    def body(k, _):
        k, sub = jax.random.split(k)
        return k, sub

    return jax.lax.scan(body, key, None, length=n)


@functools.partial(jax.jit, static_argnums=2)
def _quantize_many(xs: jax.Array, keys: jax.Array, n_bits: int) -> jax.Array:
    return jax.vmap(lambda x, k: stochastic_quantize(x, k, n_bits))(xs, keys)


@functools.partial(jax.jit, static_argnums=3)
def _quantize_log(key: jax.Array, xs: jax.Array, n: jax.Array, n_bits: int
                  ) -> tuple[jax.Array, jax.Array]:
    """The first ``n`` rows of ``xs`` quantized under the sequential key
    chain, in one dispatch: (key advanced ``n`` steps, codes). The chain
    stops advancing at ``n`` — its first ``n`` subkeys and the returned
    key are bit-identical to ``_split_chain(key, n)`` — so ``xs`` may be
    padded to a fixed length with rows whose codes are discarded, and
    one compile serves every ``n`` up to it."""
    def body(k, i):
        nk, sub = jax.random.split(k)
        return jnp.where(i < n, nk, k), sub

    key, subs = jax.lax.scan(body, key, jnp.arange(xs.shape[0]))
    return key, _quantize_many(xs, subs, n_bits)


# ---------------------------------------------------------------------------
# Reservoir sampler + replay buffer
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ReservoirSampler:
    """Algorithm-R over a stream of unknown length with the paper's hardware
    construction: counter + xorshift32 + modulus + index check.

    Every element of the stream ends up in the buffer with equal probability
    k/i after i presentations.
    """
    capacity: int
    seed: int = 0x2545F491
    # "modulus" is the paper's hardware (and the bit-stream every pinned
    # seed walks); "reject" swaps in the unbiased rejection reducer.
    rng_mode: str = "modulus"

    def __post_init__(self):
        self._rng = Xorshift32(self.seed, mode=self.rng_mode)
        self.count = 0  # the paper's counter i

    def offer(self) -> Optional[int]:
        """Present one example; return the buffer slot to overwrite, or None
        if the example is not selected."""
        self.count += 1
        i = self.count
        if i <= self.capacity:
            return i - 1
        # j uniform in [1, i] via modulus unit; keep iff j <= k.
        j = self._rng.randint(1, i)
        return j - 1 if j <= self.capacity else None


class ReplayBuffer:
    """Policy-driven, stochastically-quantized replay store.

    Features are stored as n_bits integer codes (8→4-bit halves the memory,
    §IV-A-2) in a dtype sized by :func:`code_dtype`; labels as int32.
    Host-side numpy storage — this is the DRAM replay buffer, not an
    on-device tensor, and when a :class:`~repro.telemetry.meters.Telemetry`
    accumulator is attached every insert/sample is metered as DRAM traffic
    (``replay_*`` counters).

    Slot selection is delegated to a :class:`repro.replay.ReplayPolicy`
    (a registered name or an instance). The default ``"reservoir"`` is
    the paper's §IV-A hardware bit-for-bit — identical sampler seed
    derivation, identical host-RNG consumption — so schedules built
    through the policy layer hash to the pre-refactor golden digest.

    Two ways to write. :meth:`add` / :meth:`add_batch` quantize at once
    (one device round trip per call) and :meth:`sample` reads codes.
    :meth:`stage_batch` / :meth:`sample_staged` / :meth:`commit` defer
    the quantizer: the policy and the labels advance on the host as
    before, the admitted raw rows wait in a write log, samples come back
    as *versions* (which write holds the slot), and :meth:`commit`
    quantizes the whole log in one dispatch and resolves them — the
    same codes, key chain and final state as the eager calls, bit for
    bit (tests/test_replay.py). ``build_batch_schedule`` takes the
    deferred path: one round trip a schedule instead of one a batch.
    """

    def __init__(self, capacity: int, feature_shape: tuple[int, ...],
                 n_bits: int = 4, seed: int = 7, policy=None,
                 telemetry=None):
        from repro.replay import ReplayPolicy, make_policy
        if policy is None or isinstance(policy, str):
            policy = make_policy(policy or "reservoir", capacity,
                                 seed=seed)
        if not isinstance(policy, ReplayPolicy):
            raise TypeError(f"policy must be a registered name or a "
                            f"ReplayPolicy, got {type(policy).__name__}")
        if policy.in_graph:
            raise ValueError(
                f"policy {policy.name!r} is in-graph (training-state-"
                f"dependent); it runs on the scan-carried buffer in "
                f"repro.replay.ingraph, not the host ReplayBuffer")
        if policy.capacity != capacity:
            raise ValueError(f"policy capacity {policy.capacity} != "
                             f"buffer capacity {capacity}")
        self.capacity = capacity
        self.n_bits = n_bits
        self.policy = policy
        # Back-compat alias: the reservoir policy's hardware sampler.
        self.sampler = getattr(policy, "sampler", None)
        self._feat = np.zeros((capacity, *feature_shape),
                              dtype=code_dtype(n_bits))
        self._label = np.zeros((capacity,), dtype=np.int32)
        self.size = 0
        self._qkey = jax.random.PRNGKey(seed)
        self._telemetry = telemetry
        # Running DRAM-traffic tally (meter-keyed), kept even without an
        # attached accumulator so schedule builders can credit the
        # traffic to a run's telemetry exactly once (run_continual and
        # the compiled sweep build/discard schedules at different times).
        self.traffic: dict[str, int] = {}
        # The write log of the staged path: raw admitted rows, the rows
        # offered since the last commit (the padded length of its one
        # dispatch) and each slot's version — a row of the table
        # ``commit`` returns: slot i's committed codes (row i) or staged
        # write j (row capacity + j).
        self._log: list[np.ndarray] = []
        self._n_log = 0
        self._n_offered = 0
        self._version = np.arange(capacity)

    # ------------------------------------------------------------------
    def _meter(self, *, reads: int = 0, writes: int = 0) -> None:
        """Count DRAM traffic: rows moved and bytes (codes + int32
        label per row). Host-side concrete deltas — exact, no tracing."""
        from repro.telemetry import meters as M
        row_bytes = (self._feat.dtype.itemsize
                     * int(np.prod(self._feat.shape[1:]))
                     + self._label.dtype.itemsize)
        deltas: dict[str, int] = {}
        if reads:
            deltas[M.REPLAY_READS] = reads
            deltas[M.REPLAY_READ_BYTES] = reads * row_bytes
        if writes:
            deltas[M.REPLAY_WRITES] = writes
            deltas[M.REPLAY_WRITE_BYTES] = writes * row_bytes
        for k, v in deltas.items():
            self.traffic[k] = self.traffic.get(k, 0) + v
        if self._telemetry is not None and self._telemetry.enabled:
            self._telemetry.record(deltas)

    def _require_committed(self) -> None:
        if self._n_log:
            raise RuntimeError(f"{self._n_log} staged writes pending: "
                               "commit() before an eager add or sample")

    def _select(self, ys, task_ids, valid) -> tuple[list[int], list[int]]:
        """Offer each valid row to the policy: the (slot, row) pairs of
        the admissions, in order."""
        slots: list[int] = []
        keep: list[int] = []
        for i in range(len(ys)):
            if valid is not None and not valid[i]:
                continue
            tid = int(task_ids[i]) if task_ids is not None else 0
            slot = self.policy.select_insert(int(ys[i]), tid)
            if slot is None:
                continue
            slots.append(slot)
            keep.append(i)
        return slots, keep

    def add(self, x: np.ndarray, y: int, task_id: int = 0) -> bool:
        """Offer one (features∈[0,1], label) example to the policy."""
        self._require_committed()
        slot = self.policy.select_insert(int(y), int(task_id))
        if slot is None:
            return False
        with span("replay.quantize"):
            self._qkey, sub = jax.random.split(self._qkey)
            q = np.asarray(stochastic_quantize(jnp.asarray(x), sub,
                                               self.n_bits))
        self._feat[slot] = q
        self._label[slot] = y
        self.size = self.policy.occupancy
        self._meter(writes=1)
        return True

    def add_batch(self, xs: np.ndarray, ys: np.ndarray,
                  task_ids=None, valid=None) -> int:
        """Offer a batch to the policy. Equivalent to per-example
        :meth:`add` calls bit-for-bit (same key chain, same quantizer
        draws — asserted in tests/test_replay.py), but all accepted
        examples are quantized in one vmapped dispatch instead of one
        jax call per example. (A schedule stages its batches instead —
        :meth:`stage_batch` — and pays one dispatch in all.)

        ``valid`` (a (B,) bool mask) gates padded rows out entirely:
        an invalid row is never offered to the policy and consumes no
        sampler or quantizer RNG, so a zero-padded batch leaves the
        buffer in exactly the state the unpadded batch would."""
        self._require_committed()
        slots, keep = self._select(ys, task_ids, valid)
        if not slots:
            return 0
        # The exact sequential key chain self._qkey would have walked,
        # computed in one scan dispatch; then one vmapped quantize.
        with span("replay.quantize"):
            self._qkey, subs = _split_chain(self._qkey, len(slots))
            q = np.asarray(_quantize_many(
                jnp.asarray(np.ascontiguousarray(xs[keep])), subs,
                self.n_bits))
        for slot, qi, i in zip(slots, q, keep):
            self._feat[slot] = qi
            self._label[slot] = int(ys[i])
        self.size = self.policy.occupancy
        self._meter(writes=len(slots))
        return len(slots)

    def sample(self, rng: np.random.Generator, batch: int
               ) -> tuple[np.ndarray, np.ndarray]:
        """Policy-selected sample of dequantized examples for rehearsal
        (uniform over the occupied prefix under ``reservoir``/``ring``;
        stratified under the partitioned policies). Dequantizes on the
        paper's 1/2^n scale — see :func:`round_trip_bound`."""
        self._require_committed()
        if self.size == 0:
            raise ValueError("empty replay buffer")
        idx = np.asarray(self.policy.select_sample(rng, batch))
        self._meter(reads=batch)
        return self.dequantize(self._feat[idx]), self._label[idx]

    def dequantize(self, codes: np.ndarray) -> np.ndarray:
        """Host codes → float32 features on the paper's 1/2^n scale."""
        return codes.astype(np.float32) / (2.0 ** self.n_bits)

    def stage_batch(self, xs: np.ndarray, ys: np.ndarray,
                    task_ids=None, valid=None) -> int:
        """:meth:`add_batch` with the quantizer deferred to
        :meth:`commit`. The policy's draws, the labels, the occupancy
        and the metering happen now, exactly as in :meth:`add_batch`;
        the admitted raw rows go to the write log and each written slot
        takes the write's version. Touches no device."""
        slots, keep = self._select(ys, task_ids, valid)
        self._n_offered += (len(ys) if valid is None
                            else int(np.count_nonzero(valid)))
        for slot, i in zip(slots, keep):
            self._version[slot] = self.capacity + self._n_log
            self._label[slot] = int(ys[i])
            self._n_log += 1
        if slots:
            self._log.append(xs[keep])
        self.size = self.policy.occupancy
        self._meter(writes=len(slots))
        return len(slots)

    def sample_staged(self, rng: np.random.Generator, batch: int
                      ) -> tuple[np.ndarray, np.ndarray]:
        """:meth:`sample` under staged writes: the same policy draw and
        metering, but each picked slot comes back as its current version
        — a row of the table :meth:`commit` returns — not as features."""
        if self.size == 0:
            raise ValueError("empty replay buffer")
        idx = np.asarray(self.policy.select_sample(rng, batch))
        self._meter(reads=batch)
        return self._version[idx], self._label[idx]

    def commit(self) -> np.ndarray:
        """Quantize every staged write in one dispatch and store the
        codes. The dispatch is padded to the rows offered since the last
        commit, a count fixed by the stream's shape and not by the
        policy's draws, so seeds of one stream share its compile.

        Returns the code table the staged versions index: the codes the
        slots held before staging (rows ``0..capacity-1``), then one row
        per staged write in admission order."""
        n = self._n_log
        codes = np.zeros((0, *self._feat.shape[1:]), self._feat.dtype)
        if n:
            xs = np.concatenate(self._log)
            xs = np.concatenate([xs, np.zeros(
                (self._n_offered - n, *xs.shape[1:]), xs.dtype)])
            with span("replay.quantize", rows=n):
                self._qkey, q = _quantize_log(self._qkey, jnp.asarray(xs),
                                              n, self.n_bits)
                codes = np.asarray(q)[:n]
        table = np.concatenate([self._feat, codes])
        self._feat[:] = table[self._version]
        self._version = np.arange(self.capacity)
        self._log, self._n_log, self._n_offered = [], 0, 0
        return table

    @property
    def nbytes(self) -> int:
        return self._feat.nbytes + self._label.nbytes
