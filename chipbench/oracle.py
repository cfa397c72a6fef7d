"""The benchmark's plain reference: the paper's Eq.(1)+(2) objective in
float64 numpy, on a substrate and a problem that this file builds itself.

Nothing here imports the program.  The substrate comes from a builder under
``substrates/`` (found by the preset's name) and the hardware table beside
it; routes come from a breadth-first search that visits neighbours in
wiring order, as the program's router does.  A problem is the benchmark's
own record of the live services: F [R, V] GFLOPS, H [R, V, V] Mbps and the
source node of each service's input VM (VM 0).  H may hold any graph over a
service's VMs (a chain, a fan-out, a DAG): the links are its nonzero
entries.  Services of fewer VMs are zero-padded to V, and a padding VM, with
no demand and no link, changes no objective wherever it is placed, here as
in the program; ``run.check`` takes any such pool.

``objectives`` scores a stack of placements and ``objective`` one;
``polish_residual`` reads what the best single move of one VM would still
save, and ``sweep`` is the reference's own polish pass.  ``cast`` rounds
every stage's result to a lower precision, which is how the correctness
control is computed.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

import numpy as np

HERE = Path(__file__).resolve().parent
ACTIVE_EPS = 1.0e-6     # a load at or below this is idle (no idle power)
PENALTY = 1.0e4         # watts per unit of capacity violation


@dataclass
class Substrate:
    proc_names: List[str]
    proc_layer: List[str]
    pp: Dict[str, np.ndarray]     # per processing node [P]
    nn: Dict[str, np.ndarray]     # per network node [N]
    route_idx: np.ndarray         # [P, P, K] network node ids, pad = N

    @property
    def P(self) -> int:
        return len(self.proc_names)

    @property
    def N(self) -> int:
        return self.nn["eps"].shape[0]

    def layer(self, tag: str) -> List[int]:
        return [i for i, t in enumerate(self.proc_layer) if t == tag]


def _load_builder(preset: str):
    path = HERE / "substrates" / f"{preset}.py"
    if not path.is_file():
        raise ValueError(f"no substrate builder {path}")
    spec = importlib.util.spec_from_file_location(f"substrate_{preset}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build


def routes(proc: List[str], net: List[str], edges) -> np.ndarray:
    """[P, P, K] table of the network nodes on each shortest route, from a
    breadth-first search per source that visits neighbours in the order the
    edges were wired; entries past a route's end hold N."""
    names = list(proc) + list(net)
    index = {n: i for i, n in enumerate(names)}
    nbrs: List[List[int]] = [[] for _ in names]
    for a, b in edges:
        nbrs[index[a]].append(index[b])
        nbrs[index[b]].append(index[a])
    P, N = len(proc), len(net)
    table: List[List[List[int]]] = [[[] for _ in range(P)] for _ in range(P)]
    for b in range(P):
        prev = [-1] * len(names)
        seen = [False] * len(names)
        seen[b] = True
        frontier = [b]
        while frontier:
            nxt = []
            for u in frontier:
                for v in nbrs[u]:
                    if not seen[v]:
                        seen[v] = True
                        prev[v] = u
                        nxt.append(v)
            frontier = nxt
        for e in range(P):
            if e == b or not seen[e]:
                continue
            u, hops = prev[e], []
            while u != b and u != -1:
                if u >= P:
                    hops.append(u - P)
                u = prev[u]
            table[b][e] = hops
    K = max(1, max(len(r) for row in table for r in row))
    out = np.full((P, P, K), N, np.int32)
    for b in range(P):
        for e in range(P):
            out[b, e, :len(table[b][e])] = table[b][e]
    return out


def node_layers(preset: str, args: dict):
    """(names, layer tags) of the processing nodes, without routing."""
    proc, _, _ = _load_builder(preset)(**args)
    return [p[0] for p in proc], [p[2] for p in proc]


def build_substrate(preset: str, args: dict) -> Substrate:
    hw = json.loads((HERE / "substrates" / "hardware.json").read_text())
    proc, net, edges = _load_builder(preset)(**args)
    rows = [dict(hw["processing"][cls], **over) for _, cls, _, over in proc]
    col = lambda key: np.array([r[key] for r in rows], np.float64)
    pp = dict(E=(col("max_w") - col("idle_w")) / col("cap_gflops"),
              C_pr=col("cap_gflops"), NS=col("n_servers"),
              pi_pr=col("idle_w"), pue_pr=col("pue"),
              EL=col("lan_eps_w_per_gbps"), C_lan=col("lan_cap_gbps"),
              pi_lan=col("lan_idle_w"), lan_share=col("lan_idle_share"))
    nrows = [hw["network"][cls] for _, cls in net]
    ncol = lambda key: np.array([r[key] for r in nrows], np.float64)
    nn = dict(eps=(ncol("max_w") - ncol("idle_w")) / ncol("cap_gbps"),
              C_net=ncol("cap_gbps"), pi_net=ncol("idle_w"),
              pue_net=ncol("pue"), idle_share=ncol("idle_share"))
    names = [p[0] for p in proc]
    return Substrate(proc_names=names, proc_layer=[p[2] for p in proc],
                     pp=pp, nn=nn,
                     route_idx=routes(names, [n[0] for n in net], edges))


def _f64(a):
    return np.asarray(a, np.float64)


def objectives(sub: Substrate, F, H, src, Xs,
               cast: Callable = _f64) -> Dict[str, np.ndarray]:
    """Eq.(1) network watts, Eq.(2) processing watts and the capacity
    violation of each placement Xs [B, R, V] (node per VM) of services
    F [R, V], H [R, V, V], src [R]; ``objective`` = net + proc + PENALTY *
    violation, each an array [B].  ``cast`` is applied to the inputs and to
    every stage's result: float64 by default, a lower precision for the
    control."""
    P, N = sub.P, sub.N
    Xs = np.asarray(Xs, np.int64)
    B = Xs.shape[0]
    F, H = cast(F), cast(H)
    pp = {k: cast(v) for k, v in sub.pp.items()}
    nn = {k: cast(v) for k, v in sub.nn.items()}
    off = np.arange(B)[:, None]

    def per(idx, w, n):         # bincount per placement: [B, n]
        return np.bincount((idx + off * n).reshape(-1),
                           weights=np.broadcast_to(w, idx.shape).reshape(-1),
                           minlength=B * n).reshape(B, n)

    omega = cast(per(Xs.reshape(B, -1), F.reshape(-1), P))
    r, s, d = np.nonzero(H)
    h = H[r, s, d]
    b, e = Xs[:, r, s], Xs[:, r, d]                            # [B, L]
    same = (b == e)
    theta = cast(per(b, h, P) + per(e, h, P) - per(b, h * same, P))
    ids = sub.route_idx[b, e]                                  # [B, L, K]
    hk = np.broadcast_to(h[None, :, None], ids.shape) * (ids < N)
    lam = cast(per(np.minimum(ids, N - 1).reshape(B, -1),
                   hk.reshape(B, -1), N))
    n_srv = np.ceil(omega / pp["C_pr"])
    beta = (lam > ACTIVE_EPS).astype(np.float64)
    phi = ((omega > ACTIVE_EPS) | (theta > ACTIVE_EPS)).astype(np.float64)
    per_net = cast(nn["pue_net"] * (nn["eps"] * lam / 1e3
                                    + beta * nn["idle_share"] * nn["pi_net"]))
    per_proc = cast(pp["pue_pr"] * (pp["E"] * omega + n_srv * pp["pi_pr"]
                                    + pp["EL"] * theta / 1e3
                                    + phi * pp["lan_share"] * pp["pi_lan"]))
    relu = lambda x: np.maximum(x, 0.0)
    viol = cast(relu(omega - pp["NS"] * pp["C_pr"]).sum(1)
                + relu(lam / 1e3 - nn["C_net"]).sum(1)
                + relu(theta / 1e3 - pp["C_lan"]).sum(1))
    net, proc = cast(per_net.sum(1)), cast(per_proc.sum(1))
    return dict(net=net, proc=proc, violation=viol, watts=net + proc,
                objective=cast(net + proc + PENALTY * viol))


def objective(sub: Substrate, F, H, src, X,
              cast: Callable = _f64) -> Dict[str, float]:
    """``objectives`` of the one placement X [R, V], as floats."""
    out = objectives(sub, F, H, src, np.asarray(X)[None], cast)
    return {k: float(v[0]) for k, v in out.items()}


def moves(X) -> np.ndarray:
    """The free positions (r, v) of placement X: every VM but the input VM
    (VM 0), which stays at its source."""
    R, V = np.asarray(X).shape
    return np.array([(r, v) for r in range(R) for v in range(1, V)],
                    np.int64).reshape(-1, 2)


def move_objectives(sub: Substrate, F, H, src, X, pos,
                    cast: Callable = _f64) -> np.ndarray:
    """[len(pos), P] objective of X with the VM at each position moved to
    each processing node, the others left where they are."""
    X = np.asarray(X, np.int64)
    Xs = np.repeat(X[None], len(pos) * sub.P, 0).reshape(
        len(pos), sub.P, *X.shape)
    Xs[np.arange(len(pos))[:, None], :, pos[:, 0:1], pos[:, 1:2]] = \
        np.arange(sub.P)[None, :]
    out = objectives(sub, F, H, src, Xs.reshape(-1, *X.shape), cast)
    return out["objective"].reshape(len(pos), sub.P)


def polish_residual(sub: Substrate, F, H, src, X) -> float:
    """What the best single move of one free VM would still save, as a
    share of X's objective, in float64: 0 where every free VM sits on its
    best node given the others, as a polish sweep leaves it."""
    pos = moves(X)
    if not len(pos):
        return 0.0
    cur = objective(sub, F, H, src, X)["objective"]
    best = float(move_objectives(sub, F, H, src, X, pos).min())
    return max(0.0, (cur - best) / abs(cur))


def sweep(sub: Substrate, F, H, src, X, cast: Callable = _f64) -> np.ndarray:
    """One pass over the free positions of X in row order, each VM moved to
    the node where ``cast``'s objective is least (ties to the lower node),
    the later moves seeing the earlier ones: the reference's polish sweep,
    which the control runs at a lower precision."""
    X = np.array(X, np.int64)
    for r, v in moves(X):
        X[r, v] = int(np.argmin(move_objectives(
            sub, F, H, src, X, np.array([(r, v)]), cast)[0]))
    return X


def bf16(a):
    """Round to bfloat16 and back: the control's precision, one below the
    float32 that the program computes in."""
    import ml_dtypes
    return np.asarray(a, np.float64).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def cdc_placement(sub: Substrate, F, src) -> np.ndarray:
    """The paper's all-CDC baseline: every non-input VM in a cloud
    datacenter, first-fit decreasing by GFLOPS onto the least-loaded CDC
    with room (input VMs stay at their sources)."""
    F = _f64(F)
    R, V = F.shape
    cdc = sub.layer("cdc")
    cap = sub.pp["C_pr"] * sub.pp["NS"]
    load = np.bincount(np.asarray(src), weights=F[:, 0], minlength=sub.P)
    X = np.tile(np.asarray(src, np.int64)[:, None], (1, V))
    order = sorted(((r, v) for r in range(R) for v in range(1, V)),
                   key=lambda rv: -F[rv])
    for r, v in order:
        fits = [p for p in sorted(cdc, key=lambda p: load[p])
                if load[p] + F[r, v] <= cap[p] + 1e-9]
        p = fits[0] if fits else cdc[0]
        X[r, v] = p
        load[p] += F[r, v]
    return X
