"""The synthetic German-grid data of the HVDC deployment, made from a seed.

The 2012 NEP grid of the paper (2715 buses, 5351 lines, 871 generators,
18 HVDC lines) is not public. This generator places buses in the unit
square, joins them by a random spanning tree plus nearest-neighbour lines
up to the published line count, and sets per-unit line parameters, loads,
generators and HVDC corridors (long north-south pairs). It draws the same
numbers, in the same order, as the program's own synthetic-grid generator,
so a grid seed names one grid; the arrays here are the benchmark's, and
both the program and the reference are handed them.

Units are per unit on ``base_mva``. Returns numpy arrays on the host.
"""
from __future__ import annotations

import numpy as np


def make_grid(n_bus: int, n_line: int, n_gen: int, n_hvdc: int,
              hvdc_pmax_mw, grid_seed: int, base_mva: float = 100.0,
              **_) -> dict:
    rng = np.random.default_rng(grid_seed)
    pts = rng.uniform(0, 1, size=(n_bus, 2))

    # randomized Prim tree: each new bus joins its nearest bus in the tree
    edges = set()
    order = rng.permutation(n_bus)
    tree = np.empty((n_bus, 2))
    tree[0] = pts[order[0]]
    for k, v in enumerate(order[1:], start=1):
        d = np.sum((tree[:k] - pts[v]) ** 2, axis=1)
        u = order[int(np.argmin(d))]
        edges.add((min(u, v), max(u, v)))
        tree[k] = pts[v]

    # nearest-neighbour candidates (8 per bus), shuffled, until n_line
    cand = []
    for s in range(0, n_bus, 512):
        d = np.sum((pts[s:s + 512, None] - pts[None]) ** 2, axis=2)
        rows = np.arange(s, min(s + 512, n_bus))
        d[rows - s, rows] = np.inf
        nn = np.argsort(d, axis=1)[:, :8]
        for i, row in zip(rows, nn):
            cand.extend((min(i, int(j)), max(i, int(j))) for j in row)
    rng.shuffle(cand)
    for e in cand:
        if len(edges) >= n_line:
            break
        if e[0] != e[1]:
            edges.add(e)
    edges = sorted(edges)[:n_line]
    while len(edges) < n_line:
        a, b = rng.integers(0, n_bus, 2)
        if a != b and (min(a, b), max(a, b)) not in edges:
            edges.append((min(a, b), max(a, b)))
    f_bus = np.array([e[0] for e in edges])
    t_bus = np.array([e[1] for e in edges])
    nl = len(edges)

    length = np.linalg.norm(pts[f_bus] - pts[t_bus], axis=1) + 0.02
    x = 0.25 * length * rng.uniform(0.8, 1.2, nl)
    r = x * rng.uniform(0.08, 0.15, nl)
    b_sh = 0.4 * length * rng.uniform(0.8, 1.2, nl)

    gen_buses = rng.choice(n_bus, size=n_gen, replace=False)
    cap = rng.lognormal(mean=0.0, sigma=0.8, size=n_gen)
    p_load = rng.lognormal(0.0, 0.6, n_bus)
    p_load = p_load / p_load.sum() * (0.295 * n_bus)
    q_load = p_load * rng.uniform(0.2, 0.4, n_bus)
    p_gen = np.zeros(n_bus)
    np.add.at(p_gen, gen_buses, cap / cap.sum() * p_load.sum() * 1.02)

    bus_type = np.zeros(n_bus, np.int32)                 # 0 PQ
    bus_type[gen_buses] = 1                              # 1 PV
    bus_type[gen_buses[int(np.argmax(cap))]] = 2         # 2 slack
    v_set = np.ones(n_bus)
    v_set[gen_buses] = rng.uniform(1.0, 1.03, n_gen)
    rate = np.maximum(2.0, 6.0 * length) * rng.uniform(0.9, 1.3, nl)

    hf, ht = [], []
    tries = 0
    while len(hf) < n_hvdc and tries < 10_000:
        a, b = rng.integers(0, n_bus, 2)
        if a != b and np.linalg.norm(pts[a] - pts[b]) > 0.5:
            hf.append(a)
            ht.append(b)
        tries += 1
    return dict(n_bus=n_bus, bus_type=bus_type, p_load=p_load,
                q_load=q_load, p_gen=p_gen, v_set=v_set, f_bus=f_bus,
                t_bus=t_bus, r=r, x=x, b_sh=b_sh, rate=rate,
                hvdc_f=np.asarray(hf), hvdc_t=np.asarray(ht),
                hvdc_pmax=np.asarray(hvdc_pmax_mw, np.float64) / base_mva)
