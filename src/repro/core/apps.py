"""The paper's four evaluation applications (Section 5-3, Fig. 9).

  LIT — local image thresholding (Sauvola), Eq. (5)-(6), 9x9 window
  OL  — Bayesian object location, Eq. (7), 64x64 grid, 3 sensors
  HDP — Bayesian heart-disaster prediction, Eq. (8)-(9)
  KDE — kernel density estimation, Eq. (10), N-frame history

Each application provides:
  * ``exact(...)``       — float reference
  * ``stochastic(...)``  — the SC accuracy path on packed bitstreams, with
                           optional bitflip injection (Table 4)
  * ``binary8(...)``     — the 8-bit fixed-point binary-IMC accuracy path,
                           with optional bitflip injection (Table 4)
  * ``cost_stages()``    — netlist stages (circuit, instance count) feeding
                           Algorithm 1 + the architecture model (Table 3)

Reconstruction notes (figure images unavailable): DESIGN.md §7.  The SC mean
over k operands uses a uniform-select multiplexer (unbiased k-way scaled
addition); its netlist form is the balanced MUX tree of circuits.sc_mux_tree.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import bitstream as bs
from . import circuits, executor, faults, sc_ops
from .gates import Netlist


# ------------------------------------------------------------------ helpers ----

def mean_select_stream(key: jax.Array, leaves: jax.Array, bl: int) -> jax.Array:
    """Unbiased SC mean of k streams: per bit, select one leaf uniformly.

    ``leaves``: (..., k, W) packed.  Returns (..., W) packed with value
    mean_k(values).  The hardware realization is the MUX tree (cost path);
    a uniform k-way select is its unbiased generalization.
    """
    k = leaves.shape[-2]
    bits = bs.unpack_bits(leaves)                     # (..., k, W, 32)
    sel = jax.random.randint(key, (bits.shape[-2], bs.WORD_BITS), 0, k)  # (W,32)
    sel = jnp.broadcast_to(sel, bits.shape[:-3] + sel.shape)[..., None, :, :]
    picked = jnp.take_along_axis(bits, sel, axis=-3)[..., 0, :, :]
    return bs.pack_bits(picked)


def _flip(key, words, rate, model=None):
    """Fault injection on one stored intermediate (Table-4 checkpoints).

    Each call site models one STT-MRAM array holding the stage's streams:
    transient flips under the legacy ``rate``, or the full ``FaultModel``
    (stuck-at cells, dead rows, wear) — each site draws its own masks from
    its own key, so distinct arrays fail independently."""
    if not faults.injecting(rate, model):
        return words
    return faults.apply_faults(key, words, rate, model)


def _app_fault_model(rate: float, model):
    """Normalize/validate the (bitflip_rate, fault_model) pair of one app."""
    model = faults.normalize_fault_model(model)
    if model is not None and rate > 0.0:
        raise ValueError("pass bitflip_rate or fault_model, not both "
                         "(FaultModel(flip_rate=...) subsumes bitflip_rate)")
    return model


def _value_stream(key: jax.Array, value: jax.Array, bl: int) -> jax.Array:
    return bs.generate(key, value, bl)


# Fixed-point helpers for the binary-IMC accuracy path (8-bit, Table 4).

def _q8(x: np.ndarray) -> np.ndarray:
    return np.clip(np.round(np.asarray(x) * 255.0), 0, 255).astype(np.int64)


def _dq8(x: np.ndarray) -> np.ndarray:
    return x.astype(np.float64) / 255.0


def _flip8(rng: np.random.Generator, x: np.ndarray, rate: float,
           bits: int = 8) -> np.ndarray:
    """Flip each of the low ``bits`` bits independently with prob ``rate``."""
    if rate <= 0:
        return x
    masks = rng.random(x.shape + (bits,)) < rate
    flip = (masks * (1 << np.arange(bits))).sum(axis=-1).astype(np.int64)
    return x ^ flip


# ================================ LIT ============================================

WINDOW = 9  # 9x9 window (Section 5.3.2)


def lit_exact(a: np.ndarray) -> np.ndarray:
    """Eq. (5)-(6): a has shape (..., 81) of pixel intensities in [0,1]."""
    m = a.mean(-1)
    m2 = (a * a).mean(-1)
    sigma = np.sqrt(np.abs(m2 - m * m))
    return m * (sigma + 1.0) / 2.0


def lit_stochastic(key: jax.Array, a: jax.Array, bl: int = 256,
                   bitflip_rate: float = 0.0, fault_model=None) -> jax.Array:
    """SC accuracy path for LIT.  a: (..., 81) in [0,1]; returns T estimates."""
    fault_model = _app_fault_model(bitflip_rate, fault_model)
    ks = jax.random.split(key, 16)
    a = jnp.asarray(a, jnp.float32)
    A1 = _flip(ks[10], bs.generate(ks[0], a, bl), bitflip_rate,
               fault_model)                                       # (...,81,W)
    A2 = _flip(ks[11], bs.generate(ks[1], a, bl), bitflip_rate, fault_model)

    squares = A1 & A2                                             # value a^2
    squares = _flip(ks[12], squares, bitflip_rate, fault_model)
    mean_sq = mean_select_stream(ks[2], squares, bl)              # E[a^2]
    mean_a_x = mean_select_stream(ks[3], A1, bl)
    mean_a_y = mean_select_stream(ks[4], A2, bl)
    mean_sq_of_mean = mean_a_x & mean_a_y                         # E[a]^2
    mean_sq = _flip(ks[13], mean_sq, bitflip_rate, fault_model)
    mean_sq_of_mean = _flip(ks[14], mean_sq_of_mean, bitflip_rate,
                            fault_model)

    # Absolute difference needs correlated operands: regenerate correlated
    # streams at the decoded values (StoB->BtoS regeneration, DESIGN.md §7).
    v1 = bs.to_value(mean_sq, bl)
    v2 = bs.to_value(mean_sq_of_mean, bl)
    c1, c2 = bs.generate_correlated(ks[5], [v1, v2], bl)
    var_stream = c1 ^ c2                                          # |v1 - v2|

    # sqrt: value-faithful sampling (DESIGN.md §7(e)).
    sigma_v = jnp.sqrt(bs.to_value(var_stream, bl))
    sigma_stream = bs.generate(ks[6], sigma_v, bl)
    ones = bs.generate(ks[7], jnp.ones_like(sigma_v), bl)
    half = bs.generate(ks[8], jnp.full_like(sigma_v, 0.5), bl)
    scaled = sc_ops.scaled_add(sigma_stream, ones, half)          # (sigma+1)/2
    mean_a_z = mean_select_stream(ks[9], A1, bl)
    t_stream = mean_a_z & scaled
    t_stream = _flip(ks[15], t_stream, bitflip_rate, fault_model)
    return bs.to_value(t_stream, bl)


def lit_binary8(rng: np.random.Generator, a: np.ndarray,
                bitflip_rate: float = 0.0) -> np.ndarray:
    """8-bit fixed-point binary-IMC accuracy path with bitflip injection."""
    q = _flip8(rng, _q8(a), bitflip_rate)
    sq = _flip8(rng, (q * q) >> 8, bitflip_rate, bits=8)
    m2 = _flip8(rng, sq.mean(-1).astype(np.int64), bitflip_rate)
    m = _flip8(rng, q.mean(-1).astype(np.int64), bitflip_rate)
    msq = _flip8(rng, (m * m) >> 8, bitflip_rate)
    var = _flip8(rng, np.abs(m2 - msq), bitflip_rate)
    sigma = _flip8(rng, np.sqrt(var / 255.0 * 255.0 * 255.0).astype(np.int64) % 256,
                   bitflip_rate)
    t = _flip8(rng, (m * ((sigma + 255) >> 1)) >> 8, bitflip_rate)
    return _dq8(t)


@dataclasses.dataclass(frozen=True)
class CostStage:
    netlist: Netlist
    n_instances: int         # independent circuit instances in this stage
    q_lanes: int             # SIMD lanes per instance per subarray pass


def lit_cost_stages() -> list[CostStage]:
    """Netlist stages for one window evaluation (cost path, Table 3)."""
    stages = [CostStage(circuits.sc_multiply(), 81, 1)]           # squares
    # Three mean trees (A x2 for the squared mean, squares x1), level by level.
    for _tree in range(3):
        k = 81
        while k > 1:
            pairs = k // 2
            stages.append(CostStage(circuits.sc_scaled_add(), pairs, 1))
            k = pairs + (k % 2)
    stages += [
        CostStage(circuits.sc_multiply(), 1, 1),                  # mean(A)^2
        CostStage(circuits.sc_abs_sub(), 1, 1),
        CostStage(circuits.sc_sqrt(), 1, 1),
        CostStage(circuits.sc_scaled_add(), 1, 1),                # (sigma+1)/2
        CostStage(circuits.sc_multiply(), 1, 1),                  # T
    ]
    return stages


# ================================ OL =============================================

def ol_exact(p: np.ndarray) -> np.ndarray:
    """Eq. (7): p has shape (..., 6) of conditional probabilities."""
    return np.prod(np.asarray(p), axis=-1)


def ol_stochastic(key: jax.Array, p: jax.Array, bl: int = 256,
                  bitflip_rate: float = 0.0, fault_model=None) -> jax.Array:
    fault_model = _app_fault_model(bitflip_rate, fault_model)
    ks = jax.random.split(key, 3)
    p = jnp.asarray(p, jnp.float32)
    streams = bs.generate(ks[0], p, bl)            # (..., 6, W) independent
    streams = _flip(ks[1], streams, bitflip_rate, fault_model)
    out = streams[..., 0, :]
    for i in range(1, p.shape[-1]):
        out = out & streams[..., i, :]
    out = _flip(ks[2], out, bitflip_rate, fault_model)
    return bs.to_value(out, bl)


def ol_binary8(rng: np.random.Generator, p: np.ndarray,
               bitflip_rate: float = 0.0) -> np.ndarray:
    q = _flip8(rng, _q8(p), bitflip_rate)
    out = q[..., 0]
    for i in range(1, p.shape[-1]):
        out = _flip8(rng, (out * q[..., i]) >> 8, bitflip_rate)
    return _dq8(out)


def ol_cost_stages() -> list[CostStage]:
    """Product of 6 factors: 5 multiplies in a balanced tree (3+1+1)."""
    return [
        CostStage(circuits.sc_multiply(), 3, 1),
        CostStage(circuits.sc_multiply(), 1, 1),
        CostStage(circuits.sc_multiply(), 1, 1),
    ]


# ================================ HDP ============================================

HDP_KEYS = ("p_bp", "p_cp", "p_e", "p_d", "p_ed", "p_end", "p_ned", "p_nend")


def hdp_exact(v: dict[str, np.ndarray]) -> np.ndarray:
    """Eq. (8)-(9)."""
    p_hd_ed = ((v["p_ed"] * v["p_d"] + v["p_end"] * (1 - v["p_d"])) * v["p_e"]
               + (v["p_ned"] * v["p_d"] + v["p_nend"] * (1 - v["p_d"])) * (1 - v["p_e"]))
    num = v["p_bp"] * v["p_cp"] * p_hd_ed
    den = num + (1 - v["p_bp"]) * (1 - v["p_cp"]) * (1 - p_hd_ed)
    return num / den


def hdp_stochastic(key: jax.Array, v: dict[str, jax.Array], bl: int = 256,
                   bitflip_rate: float = 0.0, fault_model=None) -> jax.Array:
    fault_model = _app_fault_model(bitflip_rate, fault_model)
    ks = jax.random.split(key, 12)
    g = {k: bs.generate(ks[i], jnp.asarray(v[k], jnp.float32), bl)
         for i, k in enumerate(HDP_KEYS)}
    if faults.injecting(bitflip_rate, fault_model):
        fk = jax.random.split(ks[8], len(HDP_KEYS))
        g = {k: _flip(fk[i], s, bitflip_rate, fault_model)
             for i, (k, s) in enumerate(g.items())}
    # Eq. (9): nested MUXes with variable selects P(D), P(E).
    inner_e = sc_ops.scaled_add(g["p_ed"], g["p_end"], g["p_d"])
    inner_ne = sc_ops.scaled_add(g["p_ned"], g["p_nend"], g["p_d"])
    # Independent select stream instances for the outer MUX:
    p_e2 = bs.generate(ks[9], jnp.asarray(v["p_e"], jnp.float32), bl)
    p_hd_ed = sc_ops.scaled_add(inner_e, inner_ne, p_e2)
    p_hd_ed = _flip(ks[10], p_hd_ed, bitflip_rate, fault_model)
    # Eq. (8): numerator / (numerator + complement term) via the JK divider.
    num = g["p_bp"] & g["p_cp"] & p_hd_ed
    # Complement streams: NOT of independent regenerations (independence for
    # the product), matching Fig. 9(c)'s separately-generated inputs.
    nbp = ~bs.generate(ks[11], jnp.asarray(v["p_bp"], jnp.float32), bl)
    ncp = ~bs.generate(jax.random.fold_in(ks[0], 7), jnp.asarray(v["p_cp"], jnp.float32), bl)
    nhd = ~bs.generate(jax.random.fold_in(ks[1], 7),
                       bs.to_value(p_hd_ed, bl), bl)
    comp = nbp & ncp & nhd
    q = sc_ops.scaled_div(num, comp, bl, warmup=True)
    return bs.to_value(q, bl)


def hdp_binary8(rng: np.random.Generator, v: dict[str, np.ndarray],
                bitflip_rate: float = 0.0) -> np.ndarray:
    q = {k: _flip8(rng, _q8(v[k]), bitflip_rate) for k in HDP_KEYS}
    mul = lambda x, y: _flip8(rng, (x * y) >> 8, bitflip_rate)
    inv = lambda x: 255 - x
    inner_e = _flip8(rng, mul(q["p_ed"], q["p_d"]) + mul(q["p_end"], inv(q["p_d"])),
                     bitflip_rate)
    inner_ne = _flip8(rng, mul(q["p_ned"], q["p_d"]) + mul(q["p_nend"], inv(q["p_d"])),
                      bitflip_rate)
    p_hd = _flip8(rng, mul(inner_e, q["p_e"]) + mul(inner_ne, inv(q["p_e"])),
                  bitflip_rate)
    num = mul(mul(q["p_bp"], q["p_cp"]), p_hd)
    den = num + mul(mul(inv(q["p_bp"]), inv(q["p_cp"])), inv(p_hd))
    out = _flip8(rng, np.where(den > 0, (num * 255) // np.maximum(den, 1), 0),
                 bitflip_rate)
    return _dq8(out)


def hdp_cost_stages() -> list[CostStage]:
    return [
        CostStage(circuits.sc_scaled_add_var(), 2, 1),   # Eq. (9) inner MUXes
        CostStage(circuits.sc_scaled_add_var(), 1, 1),   # Eq. (9) outer MUX
        CostStage(circuits.sc_multiply(), 2, 1),         # numerator products
        CostStage(circuits.sc_multiply(), 2, 1),         # complement products
        CostStage(circuits.sc_scaled_div(), 1, 1),       # Eq. (8) divider
    ]


# ================================ KDE ============================================

KDE_N = 8      # history depth (paper does not print N; documented choice)
KDE_C = 4.0    # exp(-4 |x_t - x_i|), realized as five e^{-0.8 d} stages


def kde_exact(x_t: np.ndarray, hist: np.ndarray) -> np.ndarray:
    """Eq. (10): hist shape (..., N)."""
    d = np.abs(np.asarray(x_t)[..., None] - np.asarray(hist))
    return np.exp(-KDE_C * d).mean(-1)


def kde_stochastic(key: jax.Array, x_t: jax.Array, hist: jax.Array,
                   bl: int = 256, bitflip_rate: float = 0.0,
                   fault_model=None) -> jax.Array:
    """Five independent e^{-0.8 d} factors per history term, ANDed (paper:
    "five stages of e^{-4/5 x} multiplication"); unbiasedness needs fresh
    correlated (x_t, x_i) pairs and fresh Maclaurin input copies per factor."""
    fault_model = _app_fault_model(bitflip_rate, fault_model)
    x_t = jnp.asarray(x_t, jnp.float32)
    hist = jnp.asarray(hist, jnp.float32)
    n_hist = hist.shape[-1]
    n_factors, order = 5, 5
    keys = jax.random.split(key, n_hist * n_factors * (1 + order) + 2)
    ki = 0
    terms = []
    for i in range(n_hist):
        factor = None
        for f in range(n_factors):
            xa, xb = bs.generate_correlated(keys[ki], [x_t, hist[..., i]], bl)
            ki += 1
            d = xa ^ xb                                   # |x_t - x_i|
            d = _flip(jax.random.fold_in(keys[-1], ki), d, bitflip_rate,
                      fault_model)
            copies = []
            for _ in range(order):
                # independent copies of the diff for the Maclaurin ladder
                ca, cb = bs.generate_correlated(keys[ki], [x_t, hist[..., i]], bl)
                ki += 1
                copies.append(ca ^ cb)
            e = sc_ops.exp_neg(copies, KDE_C / n_factors,
                               jax.random.fold_in(keys[ki - 1], 3), bl)
            factor = e if factor is None else (factor & e)
        terms.append(factor)
    stacked = jnp.stack(terms, axis=-2)                   # (..., N, W)
    out = mean_select_stream(keys[-2], stacked, bl)
    out = _flip(keys[-1], out, bitflip_rate, fault_model)
    return bs.to_value(out, bl)


def kde_binary8(rng: np.random.Generator, x_t: np.ndarray, hist: np.ndarray,
                bitflip_rate: float = 0.0) -> np.ndarray:
    qx = _flip8(rng, _q8(x_t), bitflip_rate)
    qh = _flip8(rng, _q8(hist), bitflip_rate)
    d = _flip8(rng, np.abs(qx[..., None] - qh), bitflip_rate)
    # e^{-0.8 u} Maclaurin (5th order) in Q8, then 5 multiplies.
    u = d.astype(np.float64) / 255.0
    e1 = np.zeros_like(u)
    acc = np.ones_like(u)
    fact = 1.0
    for k in range(6):
        if k > 0:
            fact *= k
        e1 = e1 + ((-0.8 * u) ** k) / fact
    e1 = _flip8(rng, _q8(np.clip(e1, 0, 1)), bitflip_rate)
    out = e1
    for _ in range(4):
        out = _flip8(rng, (out * e1) >> 8, bitflip_rate)
    pdf = _flip8(rng, out.mean(-1).astype(np.int64), bitflip_rate)
    return _dq8(pdf)


def kde_cost_stages() -> list[CostStage]:
    stages = []
    n_factors = 5
    # Per history term: 5 factors x (1 abs-sub + 5 Maclaurin copies' abs-subs
    # + exp ladder) + 4 product ANDs; instances batched across the N terms.
    stages.append(CostStage(circuits.sc_abs_sub(), KDE_N * n_factors * 5, 1))
    stages.append(CostStage(circuits.sc_exp(KDE_C / n_factors), KDE_N * n_factors, 1))
    stages.append(CostStage(circuits.sc_multiply(), KDE_N * (n_factors - 1), 1))
    # Mean tree over N terms.
    k = KDE_N
    while k > 1:
        pairs = k // 2
        stages.append(CostStage(circuits.sc_scaled_add(), pairs, 1))
        k = pairs + (k % 2)
    return stages


# ================== composed per-bit netlist execution ===========================

def appnet_inputs(app: str, *, a=None, p=None, v=None, x_t=None,
                  hist=None) -> dict:
    """Map app-level inputs to the PI value keys of ``appnet.APP_NETLISTS``.

    Shapes (trailing dims consumed, leading dims broadcast as batch):
      lit: ``a`` (..., 81) window pixels      ol: ``p`` (..., 16, 6) pixel probs
      hdp: ``v`` dict over HDP_KEYS, or       kde: ``x_t`` (...), ``hist`` (..., N)
           an array (..., 8) whose last
           axis is in HDP_KEYS order

    Values stay *host* float32 (numpy): per-PI splats of an 81-pixel window
    would otherwise dispatch one device op per element, and host scalars are
    what the executor's bank path packs into a single per-slot vector at the
    jit boundary.  An input already on device is kept there and splats via
    device slices.
    """
    def _host(x):
        return x if isinstance(x, jax.Array) else np.asarray(x, np.float32)

    if app == "lit":
        a = _host(a)
        return {f"a{i}": a[..., i] for i in range(a.shape[-1])}
    if app == "ol":
        p = _host(p)
        return {f"p{r}_{j}": p[..., r, j]
                for r in range(p.shape[-2]) for j in range(p.shape[-1])}
    if app == "hdp":
        if isinstance(v, dict):
            return {k: _host(v[k]) for k in HDP_KEYS}
        v = _host(v)
        if v.shape[-1:] != (len(HDP_KEYS),):
            raise ValueError(f"hdp: v as an array must be (..., "
                             f"{len(HDP_KEYS)}) in HDP_KEYS order, got "
                             f"shape {v.shape}")
        return {k: v[..., i] for i, k in enumerate(HDP_KEYS)}
    if app == "kde":
        hist = _host(hist)
        vals = {f"h{i}": hist[..., i] for i in range(hist.shape[-1])}
        vals["x_t"] = _host(x_t)
        return vals
    raise KeyError(app)


def appnet_stochastic(app: str, key: jax.Array, bl: int = 256,
                      backend: str | None = None, bitflip_rate: float = 0.0,
                      flip_key: jax.Array | None = None,
                      net: Netlist | None = None, fault_model=None,
                      **inputs) -> dict[str, jax.Array]:
    """Execute the composed per-bit application netlist end to end.

    This is the cost-path netlist (``appnet.APP_NETLISTS`` — the circuit
    Algorithm 1 actually schedules) *run* through the executor's compiled
    plan: every gate level becomes one fused bit-parallel pass, sequential
    state (HDP's divider) scans over words.  Returns decoded output values.

    Pass ``net`` to reuse a built netlist across calls (appnet node names are
    uniquified per build, so reuse keeps the plan/jit caches warm).
    """
    from .appnet import APP_NETLISTS
    if net is None:
        net = APP_NETLISTS[app]()
    values = appnet_inputs(app, **inputs)
    return executor.execute_value(net, values, key, bl,
                                  bitflip_rate=bitflip_rate, flip_key=flip_key,
                                  backend=backend, fault_model=fault_model)


def appnet_stochastic_many(requests, key, bl: int = 256,
                           backend: str | None = None,
                           bitflip_rate: float = 0.0, flip_keys=None,
                           nets: "list[Netlist] | None" = None) -> list:
    """Serve N concurrent app evaluations as ONE fused bank-level plan.

    ``requests``: sequence of ``(app, inputs)`` pairs — ``app`` one of
    ``APPS``, ``inputs`` the keyword dict ``appnet_inputs`` expects.  The
    member netlists (heterogeneous — e.g. 4 LIT windows + 2 OL tiles + an HDP
    query) merge into one bank plan (``core/plan.compile_bank_plan``): every
    gate level is type-batched *across* requests and the whole bank runs as a
    single jit dispatch instead of one ``execute`` per request — the paper's
    Fig. 8 bank-level SIMD, and the serving path for many concurrent app
    requests per device.  ``key`` may be one key (split N ways) or N keys;
    results are bit-identical to per-request ``appnet_stochastic`` calls with
    the same per-member keys.  Pass ``nets`` to reuse built netlists across
    calls (keeps the bank-plan/jit caches warm).  Returns one decoded-output
    dict per request, in request order.
    """
    from .appnet import APP_NETLISTS
    if nets is None:
        nets = [APP_NETLISTS[app]() for app, _ in requests]
    values = [appnet_inputs(app, **inp) for app, inp in requests]
    n = len(nets)
    keys = executor._normalize_keys(key, n)
    if bitflip_rate > 0.0:
        flip_keys = executor._normalize_keys(flip_keys, n, "flip_keys")
    shared = executor.ExecOptions(backend=backend, bitstream_length=bl,
                                  bitflip_rate=bitflip_rate, decode=True)
    return executor.run(
        [executor.ExecRequest(net, vals, keys[i],
                              dataclasses.replace(
                                  shared, flip_key=flip_keys[i])
                              if bitflip_rate > 0.0 else shared)
         for i, (net, vals) in enumerate(zip(nets, values))])


def cost_stage_netlists(app: str, max_instances: int | None = None) -> list:
    """Expand an app's ``cost_stages()`` into per-instance bank members.

    Every stage instance becomes one member (repeating the stage's netlist
    object — structure-equal members intern to one compiled plan), so
    ``compile_bank_plan(cost_stage_netlists(app))`` is the bank-level plan of
    the whole Table-3 application: all same-type gates of a level across all
    stage instances fire in one pass (``arch.evaluate_bank_plan`` maps the
    pass counts onto the [n, m] bank cycle model).
    """
    stages_fn = {"lit": lit_cost_stages, "ol": ol_cost_stages,
                 "hdp": hdp_cost_stages, "kde": kde_cost_stages}[app]
    nets = []
    for st in stages_fn():
        k = st.n_instances if max_instances is None \
            else min(st.n_instances, max_instances)
        nets.extend([st.netlist] * k)
    return nets


# ============================== registry =========================================

APPS = ("lit", "ol", "hdp", "kde")
