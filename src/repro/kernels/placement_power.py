"""Pallas TPU kernels for the CFN placement objective (paper Eq. 1+2).

Two kernels share the Eq.(1)/(2) math:

  * ``placement_power_tpu`` -- batched FULL evaluation: each grid step
    evaluates a [bc]-candidate block entirely in VMEM (one-hot compares on
    the VPU, the route lookup as one matmul on the MXU).  Used when a whole
    placement changes (genetic crossover, exhaustive enumeration) and as
    the oracle-checked reference kernel.

  * ``fused_anneal_tpu`` -- the solver hot loop.  Simulated annealing
    mutates ONE VM per Metropolis step, so instead of launching a full
    [bc]-candidate evaluation per step, this kernel keeps the per-chain
    placement AND its live load tensors (omega[P], theta[P], lam[N], obj)
    resident in VMEM and fuses proposal -> delta-evaluation -> accept across
    the entire chain: one launch for the whole schedule.  The delta math
    mirrors core.power's incremental engine (the processing terms move only
    at the source/destination node; the network terms only along the two
    touched routes), expressed as one-hot compares and contractions so it
    vectorizes over the [bc] chains in a block.  Proposals (free-VM index,
    destination, uniform draw) are precomputed outside and streamed from
    VMEM.

Routes enter both kernels as the route incidence table ``route_inc
[P*P, N]`` (``route_incidence``: row b*P+e counts the network nodes on the
b->e route), expanded once outside the kernel from the problem's
padded-CSR ``route_idx``.  A route lookup weighted by bitrate is then one
[bc, P*P] x [P*P, N] matmul.  On the TPU the minor dimension of a VMEM
array pads to 128 lanes, so for N <= 128 this table takes exactly the
VMEM that the CSR table ``[P*P, K]`` would, and it saves the per-step
expansion of K ids against an N-iota.  The table stays VMEM-resident for
the whole launch, which bounds the substrate size the fused kernel can
take: see ``fused_fits``.

Layouts follow what Mosaic lowers: every in-kernel array is 2-D or has
its extra axis leading (incident-link slots ``[D, bc, 1]``, links
``[L, bc, 1]``), the per-step proposal reads are dynamic indices on a
leading axis (``[T/128, C, 128]`` chunks) followed by a lane select, and
problem constants sit whole in VMEM, loaded once.  Oracles:
kernels/ref.py::placement_objective_ref for the full kernel,
ref.placement_delta_ref (float64) for the fused deltas; core.power
re-evaluation pins the fused kernel's reported best objective.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mirrors core.power (kernels stay import-clean of core).
ACTIVE_EPS = 1.0e-6
PENALTY = 1.0e4
SNAP_GFLOPS = 1.0e-3
SNAP_MBPS = 1.0e-2

LANES = 128      # TPU vector lane width: minor dims pad to a multiple
SUBLANES = 8     # f32/int32 second-minor tile
# The fused kernel's compile envelope on a TPU v5e, from compiles for the
# chip (tests/test_tpu_compile.py compiles its corners).  Two VMEM pools
# bind: the route table sits whole in the chip's 128 MiB of VMEM, and the
# blocks plus per-step temporaries ([bc, P*P] one-hots, [D, bc, J]
# incident-slot gathers, [T/128, bc, 128] proposal chunks) must fit
# Mosaic's default 16 MiB scoped limit, which J, D, J*D and the step
# count T share.  With a 32 MiB table (P=256 at N <= 128) and T=16384,
# (J, D) = (8192, 8) and (4096, 16) compile; (16384, 4) at T=12000,
# (512, 128), and T=100000 even at paper scale exceed the scoped limit,
# and P=468 (a 107 MiB table) exceeds the chip's VMEM.
FUSED_ROUTE_VMEM_BYTES = 32 * 1024 * 1024
FUSED_MAX_J, FUSED_MAX_D, FUSED_MAX_JD = 8192, 16, 65536
FUSED_MAX_T = 16384
_HIGHEST = jax.lax.Precision.HIGHEST
_VMEM = pl.BlockSpec(memory_space=pltpu.VMEM)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def route_table_bytes(P: int, N: int) -> int:
    """VMEM bytes of the f32 route incidence table ``[P*P, N]`` as the TPU
    lays it out (rows pad to 8 sublanes, columns to 128 lanes)."""
    return _round_up(P * P, SUBLANES) * _round_up(N, LANES) * 4


def fused_fits(P: int, N: int, J: int, D: int, T: int) -> bool:
    """The fused anneal kernel's size rule: its VMEM-resident route table
    ``[P*P, N]`` f32, padded as the TPU lays it out, must fit
    ``FUSED_ROUTE_VMEM_BYTES``; the VM count J, the incidence degree D,
    J*D and the step count T must stay within ``FUSED_MAX_J``,
    ``FUSED_MAX_D``, ``FUSED_MAX_JD`` and ``FUSED_MAX_T``.  The CSR pad K
    does not enter, since the kernel never holds the CSR table.  The paper
    substrate (P=23, N=13) needs a 0.26 MiB table and ``city_scale()``
    (P=252, N=86) 31 MiB, both admitted; ``city_scale`` at P=468 (N=126,
    K=14) needs 107 MiB and is refused."""
    return (route_table_bytes(P, N) <= FUSED_ROUTE_VMEM_BYTES
            and J <= FUSED_MAX_J and D <= FUSED_MAX_D
            and J * D <= FUSED_MAX_JD and T <= FUSED_MAX_T)


def route_incidence(route_idx: jax.Array, N: int) -> jax.Array:
    """Padded-CSR routes ``[P, P, K]`` (sentinel N) -> incidence counts
    ``[P*P, N]`` float32: entry (b*P+e, n) counts node n on the b->e route."""
    P, _, K = route_idx.shape
    rows = jnp.repeat(jnp.arange(P * P), K)
    out = jnp.zeros((P * P, N + 1), jnp.float32)
    out = out.at[rows, route_idx.reshape(-1)].add(1.0)
    return out[:, :N]


def mask_proposals(j_prop, p_prop, eligible, V: int):
    """Project precomputed proposal destinations onto per-row eligible sets.

    The fused anneal kernel streams its Metropolis proposals from VMEM
    rather than sampling in-kernel, so SLA eligibility
    (``repro.api.PlacementSpec.masks``) enters the kernel as proposal
    masking HERE: any destination outside its service row's eligible set is
    replaced by that row's first eligible node before the stream reaches
    the kernel, so the chain can never be asked to accept an ineligible
    move.  Upstream samplers (``core.solvers._anneal_proposals``) already
    draw from the eligible set, making this the kernel-side guarantee
    rather than the primary sampler.

    j_prop/p_prop [C, T] (flat free-VM index, destination node);
    eligible [R, P] bool; V = VMs per service (flat index stride).
    """
    el = jnp.asarray(eligible)
    rows = j_prop // V
    ok = el[rows, p_prop]
    fallback = jnp.argmax(el, axis=1).astype(p_prop.dtype)
    return jnp.where(ok, p_prop, fallback[rows])


def _rows(params, n: int):
    """Split a [n, X] parameter stack into n broadcastable [1, X] rows."""
    return tuple(params[i:i + 1] for i in range(n))


def _power_terms(omega, theta, lam, pp, nn):
    """Eq.(1)/(2) from loads of a [bc]-block.

    omega/theta [bc, P], lam [bc, N]; pp [9, P]; nn [5, N].
    Returns (objective, net, proc, violation) each [bc, 1].
    """
    E, C_pr, NS, pi_pr, pue_pr, EL, C_lan, pi_lan, lan_share = _rows(pp, 9)
    eps, C_net, pi_net, pue_net, idle_share = _rows(nn, 5)
    n_srv = jnp.ceil(omega / C_pr)
    beta = (lam > ACTIVE_EPS).astype(jnp.float32)
    phi = ((omega > ACTIVE_EPS) | (theta > ACTIVE_EPS)).astype(jnp.float32)
    per_net = pue_net * (eps * lam / 1e3 + beta * idle_share * pi_net)
    per_proc = pue_pr * (E * omega + n_srv * pi_pr
                         + EL * theta / 1e3 + phi * lan_share * pi_lan)
    relu = lambda x: jnp.maximum(x, 0.0)
    total = lambda x: jnp.sum(x, axis=-1, keepdims=True)
    violation = (total(relu(omega - NS * C_pr))
                 + total(relu(lam / 1e3 - C_net))
                 + total(relu(theta / 1e3 - C_lan)))
    net = total(per_net)
    proc = total(per_proc)
    return net + proc + PENALTY * violation, net, proc, violation


def _kernel(x_ref, u_ref, w_ref, f_ref, h_ref, inc_ref, pp_ref, nn_ref,
            out_ref, *, P: int, bc: int):
    """Full evaluation of a [bc]-candidate block.

    x_ref [J, bc, 1] placements; u_ref/w_ref [L, bc, 1] link-endpoint
    placements; f_ref [J, 1, 1] GFLOPS; h_ref [L, 1, 1] Mbps; inc_ref
    [P*P, N] route incidence.  Writes out_ref [bc, 4]."""
    X, U, W = x_ref[...], u_ref[...], w_ref[...]
    F, H = f_ref[...], h_ref[...]
    iota_p = jax.lax.broadcasted_iota(jnp.int32, (1, bc, P), 2)
    oh_u = (U == iota_p).astype(jnp.float32)                     # [L,bc,P]
    oh_w = (W == iota_p).astype(jnp.float32)
    omega = jnp.sum(F * (X == iota_p).astype(jnp.float32), axis=0)
    # theta: traffic touching node p (in + out minus double-counted
    # intra-node traffic)
    theta = jnp.sum(H * (oh_u + oh_w - oh_u * oh_w), axis=0)     # [bc, P]
    # lam: bitrate-weighted route one-hots, then one incidence matmul
    iota_pp = jax.lax.broadcasted_iota(jnp.int32, (1, bc, P * P), 2)
    w_rt = jnp.sum(H * (iota_pp == U * P + W).astype(jnp.float32), axis=0)
    lam = jnp.dot(w_rt, inc_ref[...], precision=_HIGHEST,
                  preferred_element_type=jnp.float32)            # [bc, N]
    obj, net, proc, violation = _power_terms(omega, theta, lam,
                                             pp_ref[...], nn_ref[...])
    out_ref[:, 0:1] = obj
    out_ref[:, 1:2] = net
    out_ref[:, 2:3] = proc
    out_ref[:, 3:4] = violation


def placement_power_tpu(X: jax.Array, link_src: jax.Array,
                        link_dst: jax.Array, F: jax.Array, H: jax.Array,
                        route_inc: jax.Array, proc_params: jax.Array,
                        net_params: jax.Array, *, bc: int = 8,
                        interpret: bool = False) -> jax.Array:
    """Evaluate B candidate placements.

    X [B, J=R*V] int32 (pins already applied); link_src/dst [L] indices into
    the flattened VM space; F [J] GFLOPS; H [L] Mbps; route_inc [P*P, N]
    float32 route incidence (``route_incidence``); proc_params [9, P];
    net_params [5, N].  Returns [B, 4]: (objective, net W, proc W,
    violation).
    """
    B, J = X.shape
    L = link_src.shape[0]
    P = proc_params.shape[1]
    bc = min(bc, max(B, 1))
    pad = (-B) % bc
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
    Bp = B + pad
    col = lambda a: a.T[:, :, None]                   # [Bp, n] -> [n, Bp, 1]
    U = col(jnp.take(X, link_src, axis=1))
    W = col(jnp.take(X, link_dst, axis=1))

    blk = lambda n: pl.BlockSpec((n, bc, 1), lambda i: (0, i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, P=P, bc=bc),
        grid=(Bp // bc,),
        in_specs=[blk(J), blk(L), blk(L)] + [_VMEM] * 5,
        out_specs=pl.BlockSpec((bc, 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 4), jnp.float32),
        interpret=interpret,
    )(col(X), U, W, F.reshape(J, 1, 1), H.reshape(L, 1, 1), route_inc,
      proc_params, net_params)
    return out[:B]


def pack_problem(problem) -> Tuple[jax.Array, ...]:
    """Flatten a core.power.PlacementProblem into kernel operands."""
    p = problem
    proc_params = jnp.stack([p.E, p.C_pr, p.NS, p.pi_pr, p.pue_pr,
                             p.EL, p.C_lan, p.pi_lan, p.lan_share])
    net_params = jnp.stack([p.eps, p.C_net, p.pi_net, p.pue_net,
                            p.idle_share])
    F = p.F.reshape(-1)
    return (p.link_src, p.link_dst, F, p.link_h,
            route_incidence(p.route_idx, p.N), proc_params, net_params)


# ---------------------------------------------------------------------------
# Fused annealing kernel
# ---------------------------------------------------------------------------

def _fused_kernel(x_ref, j_ref, pn_ref, un_ref, temps_ref,
                  f_ref, io_ref, ih_ref, is_ref, inc_ref, pp_ref,
                  nn_ref, om_ref, th_ref, lm_ref, ob_ref, bx_ref, stat_ref,
                  hh_sc, idx_sc, *,
                  P: int, J: int, T: int, bc: int):
    """Whole Metropolis chain for a [bc]-chain block, state in VMEM.

    All per-step gathers are iota-compare one-hots + reductions, so they
    vectorize on TPU (no dynamic scatter/gather).  Proposal step t lives in
    chunk t // 128, lane t % 128 of the ``[T/128, bc, 128]`` streams.  The
    incident-link slots of the moved VM ride a leading axis ([D, bc, 1];
    the removal and insertion legs together [2D, bc, 1]).  The touched
    routes of all legs sum into one bitrate-weighted one-hot row over the
    P*P ordered pairs (a loop over legs, staged through the ``hh_sc`` /
    ``idx_sc`` scratch), and one matmul with the incidence table gives the
    network-load delta.  Initial loads (omega/theta/lam/obj) are computed
    outside the kernel (one batched evaluation) and streamed in."""
    F = f_ref[...]                                   # [1, J]
    inc_o = io_ref[...]                              # [D, 1, J] other VM
    inc_h = ih_ref[...]                              # [D, 1, J] bitrate
    inc_s = is_ref[...]                              # [D, 1, J] 1.0 if j is src
    E, C_pr, NS, pi_pr, pue_pr, EL, C_lan, pi_lan, lan_share = \
        _rows(pp_ref[...], 9)
    eps_n, C_net, pi_net, pue_net, idle_share = _rows(nn_ref[...], 5)
    cap_pr = NS * C_pr
    share_pi = lan_share * pi_lan
    relu = lambda x: jnp.maximum(x, 0.0)
    snap = lambda x, e: jnp.where(jnp.abs(x) < e, 0.0, x)
    rsum = lambda x, axis=1: jnp.sum(x, axis=axis, keepdims=True)

    def entry_proc(om, th, Ep, Cp, pip, puep, ELp, spp):
        """per_proc at one gathered node; all operands [bc, 1]."""
        phi = ((om > ACTIVE_EPS) | (th > ACTIVE_EPS)).astype(jnp.float32)
        return puep * (Ep * om + jnp.ceil(om / Cp) * pip + ELp * th / 1e3
                       + phi * spp)

    def step(t, carry):
        X, omega, theta, lam, obj, bX, bobj = carry
        chunk = t // LANES
        lane = jax.lax.broadcasted_iota(jnp.int32, (bc, LANES), 1) \
            == t % LANES
        pick = lambda ref: rsum(jnp.where(lane, ref[chunk], 0))
        j, p_new, u = pick(j_ref), pick(pn_ref), pick(un_ref)   # [bc, 1]
        Tt = rsum(jnp.where(lane[:1], temps_ref[pl.ds(chunk, 1), :], 0.0))

        iota_J = jax.lax.broadcasted_iota(jnp.int32, (bc, J), 1)
        ohj = iota_J == j                                        # [bc, J]
        ohj_f = ohj.astype(jnp.float32)
        p_old = rsum(jnp.where(ohj, X, 0))                       # [bc, 1]
        F_j = rsum(ohj_f * F)
        # incident-link slots of VM j, one per leading index: [D, bc, 1]
        hk = rsum(ohj_f[None] * inc_h, axis=2)
        sk = rsum(ohj_f[None] * inc_s, axis=2)
        ok = rsum(jnp.where(ohj[None], inc_o, 0), axis=2)
        is_self = ok == j[None]
        q = rsum(jnp.where(iota_J[None] == ok, X[None], 0), axis=2)
        q_rm = jnp.where(is_self, p_old[None], q)
        q_in = jnp.where(is_self, p_new[None], q)

        iota_P = jax.lax.broadcasted_iota(jnp.int32, (bc, P), 1)
        oh_po = (iota_P == p_old).astype(jnp.float32)            # [bc, P]
        oh_pn = (iota_P == p_new).astype(jnp.float32)
        # signed bitrates: -h on the removal leg, +h on the insertion leg
        hh = jnp.concatenate([-hk, hk], axis=0)                  # [2D,bc,1]
        q2 = jnp.concatenate([q_rm, q_in], axis=0)
        H_tot = jnp.sum(hk, axis=0)                              # [bc, 1]
        same_r = jnp.sum((q_rm == p_old[None]).astype(jnp.float32) * hk,
                         axis=0)
        same_i = jnp.sum((q_in == p_new[None]).astype(jnp.float32) * hk,
                         axis=0)
        d_theta = ((H_tot - same_i) * oh_pn - (H_tot - same_r) * oh_po
                   + jnp.sum(hh * (iota_P[None] == q2).astype(jnp.float32),
                             axis=0))
        # routes: ordered endpoint pair -> row of the incidence table
        sk2 = jnp.concatenate([sk, sk], axis=0) > 0.5
        a2 = jnp.concatenate(
            [jnp.broadcast_to(p_old[None], q.shape),
             jnp.broadcast_to(p_new[None], q.shape)], axis=0)
        # one leg at a time through VMEM scratch, so the [bc, P*P] one-hot
        # temporaries do not grow with the incidence degree D
        hh_sc[...] = hh
        idx_sc[...] = jnp.where(sk2, a2 * P + q2, q2 * P + a2)  # [2D,bc,1]

        def leg(l, w):
            iota_PP = jax.lax.broadcasted_iota(jnp.int32, (bc, P * P), 1)
            return w + hh_sc[l] * (iota_PP == idx_sc[l]).astype(jnp.float32)
        w_rt = jax.lax.fori_loop(0, hh.shape[0], leg,
                                 jnp.zeros((bc, P * P), jnp.float32))
        d_lam = jnp.dot(w_rt, inc_ref[...], precision=_HIGHEST,
                        preferred_element_type=jnp.float32)      # [bc, N]

        omega2 = snap(omega + F_j * (oh_pn - oh_po), SNAP_GFLOPS)
        theta2 = snap(theta + d_theta, SNAP_MBPS)
        lam2 = snap(lam + d_lam, SNAP_MBPS)

        # delta objective: processing terms at the two touched nodes only
        d_proc = jnp.zeros((bc, 1), jnp.float32)
        d_viol = jnp.zeros((bc, 1), jnp.float32)
        for oh in (oh_po, oh_pn):
            at = lambda vec: rsum(oh * vec)
            Ep, Cp, pip, puep = at(E), at(C_pr), at(pi_pr), at(pue_pr)
            ELp, spp, capp, Clp = at(EL), at(share_pi), at(cap_pr), at(C_lan)
            om_o, om_n = at(omega), at(omega2)
            th_o, th_n = at(theta), at(theta2)
            d_proc += (entry_proc(om_n, th_n, Ep, Cp, pip, puep, ELp, spp)
                       - entry_proc(om_o, th_o, Ep, Cp, pip, puep, ELp, spp))
            d_viol += (relu(om_n - capp) - relu(om_o - capp)
                       + relu(th_n / 1e3 - Clp) - relu(th_o / 1e3 - Clp))
        beta_d = ((lam2 > ACTIVE_EPS).astype(jnp.float32)
                  - (lam > ACTIVE_EPS).astype(jnp.float32))
        d_net = rsum(pue_net * (eps_n * (lam2 - lam) / 1e3
                                + beta_d * idle_share * pi_net))
        d_viol += rsum(relu(lam2 / 1e3 - C_net) - relu(lam / 1e3 - C_net))
        delta = d_proc + d_net + PENALTY * d_viol                # [bc, 1]

        acc = (delta < 0) | (u < jnp.exp(-jnp.maximum(delta, 0.0)
                                         / jnp.maximum(Tt, 1e-9)))
        X = jnp.where(acc & ohj, p_new, X)
        omega = jnp.where(acc, omega2, omega)
        theta = jnp.where(acc, theta2, theta)
        lam = jnp.where(acc, lam2, lam)
        obj = jnp.where(acc, obj + delta, obj)
        better = obj < bobj
        bX = jnp.where(better, X, bX)
        bobj = jnp.where(better, obj, bobj)
        return X, omega, theta, lam, obj, bX, bobj

    X0, obj0 = x_ref[...], ob_ref[...]
    init = (X0, om_ref[...], th_ref[...], lm_ref[...], obj0, X0, obj0)
    _, _, _, _, obj, bX, bobj = jax.lax.fori_loop(0, T, step, init)
    bx_ref[...] = bX
    stat_ref[:, 0:1] = bobj
    stat_ref[:, 1:2] = obj


def _chunk_steps(a: jax.Array, fill) -> jax.Array:
    """[C, T] per-step stream -> [ceil(T/128), C, 128] step chunks (the
    tail past T is ``fill`` and never read)."""
    C, T = a.shape
    a = jnp.pad(a, ((0, 0), (0, (-T) % LANES)), constant_values=fill)
    return a.reshape(C, -1, LANES).transpose(1, 0, 2)


def fused_anneal_tpu(X: jax.Array, j_prop: jax.Array, p_prop: jax.Array,
                     u_prop: jax.Array, temps: jax.Array,
                     inc_other: jax.Array, inc_h: jax.Array,
                     inc_src: jax.Array,
                     omega0: jax.Array, theta0: jax.Array, lam0: jax.Array,
                     obj0: jax.Array,
                     F: jax.Array, route_inc: jax.Array,
                     proc_params: jax.Array, net_params: jax.Array, *,
                     bc: int = 8, interpret: bool = False):
    """Run full Metropolis chains in one kernel launch.

    X [C, J] int32 starting placements (pins applied); j_prop/p_prop/u_prop
    [C, T] per-step proposals; temps [T]; inc_* [J, D] per-VM incident-link
    tables (core.power.build_aux); omega0/theta0 [C, P], lam0 [C, N],
    obj0 [C] the starting loads/objective (kernels.ops computes them with
    one batched evaluation); route_inc [P*P, N] float32 route incidence
    (``route_incidence``).  Returns (best_X [C, J] int32, stats [C, 2] =
    (best objective, final objective)).

    Raises ``ValueError`` before lowering when the substrate is too large
    for the kernel's VMEM-resident route table (``fused_fits``).
    """
    C, J = X.shape
    T = temps.shape[0]
    D = inc_h.shape[1]
    P = proc_params.shape[1]
    N = net_params.shape[1]
    if not fused_fits(P, N, J, D, T):
        raise ValueError(
            f"fused anneal kernel does not fit VMEM at P={P}, N={N}, J={J}, "
            f"D={D}, T={T}: a {route_table_bytes(P, N) / 2**20:.1f} MiB "
            f"route table (limit {FUSED_ROUTE_VMEM_BYTES / 2**20:.0f} MiB), "
            f"limits J <= {FUSED_MAX_J}, D <= {FUSED_MAX_D}, J*D <= "
            f"{FUSED_MAX_JD}, T <= {FUSED_MAX_T}; see fused_fits, and use "
            f"the 'delta' anneal backend")
    bc = min(bc, max(C, 1))
    pad = (-C) % bc
    obj0 = obj0[:, None]
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
        j_prop = jnp.pad(j_prop, ((0, pad), (0, 0)))
        p_prop = jnp.pad(p_prop, ((0, pad), (0, 0)))
        u_prop = jnp.pad(u_prop, ((0, pad), (0, 0)), constant_values=1.0)
        omega0 = jnp.pad(omega0, ((0, pad), (0, 0)))
        theta0 = jnp.pad(theta0, ((0, pad), (0, 0)))
        lam0 = jnp.pad(lam0, ((0, pad), (0, 0)))
        obj0 = jnp.pad(obj0, ((0, pad), (0, 0)))
    Cp = C + pad
    slots = lambda a: a.T.reshape(D, 1, J)            # [J, D] -> [D, 1, J]

    row = lambda i: (i, 0)
    chunks = pl.BlockSpec((pl.cdiv(T, LANES), bc, LANES), lambda i: (0, i, 0))
    bX, stats = pl.pallas_call(
        functools.partial(_fused_kernel, P=P, J=J, T=T, bc=bc),
        grid=(Cp // bc,),
        in_specs=[pl.BlockSpec((bc, J), row), chunks, chunks, chunks]
        + [_VMEM] * 8
        + [pl.BlockSpec((bc, P), row), pl.BlockSpec((bc, P), row),
           pl.BlockSpec((bc, N), row), pl.BlockSpec((bc, 1), row)],
        out_specs=[
            pl.BlockSpec((bc, J), row),
            pl.BlockSpec((bc, 2), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Cp, J), jnp.int32),
            jax.ShapeDtypeStruct((Cp, 2), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((2 * D, bc, 1), jnp.float32),
                        pltpu.VMEM((2 * D, bc, 1), jnp.int32)],
        interpret=interpret,
    )(X, _chunk_steps(j_prop, 0), _chunk_steps(p_prop, 0),
      _chunk_steps(u_prop, 1.0), _chunk_steps(temps[None], 1.0)[:, 0],
      F[None], slots(inc_other), slots(inc_h), slots(inc_src), route_inc,
      proc_params, net_params, omega0, theta0, lam0, obj0)
    return bX[:C], stats[:C]


def pack_aux(aux) -> Tuple[jax.Array, ...]:
    """Flatten a core.power.PlacementAux into fused-kernel operands."""
    return (aux.inc_other.astype(jnp.int32),
            aux.inc_h.astype(jnp.float32),
            aux.inc_src.astype(jnp.float32))
