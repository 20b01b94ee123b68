"""Hybrid level gather/scatter microbenchmark: the row-traffic side.

The hybrid octree matvec moves data between the flat local dof rows and
the per-level block lattices twice per level per matvec:

    gather:  jnp.take of (rows, 3) from the padded node-row table
    scatter: vmap'd  y.at[idx].add(rows)  back into the dof vector

TPU lowers arbitrary indexed reads/writes far less efficiently than
dense math (parallel/structured.py measured per-ELEMENT gathers at
~28 ms for 1.2M rows at 160k dofs).  Whether the hybrid's per-NODE
row traffic is the octree flagship's bottleneck decides the next
optimization (level-owned contiguous node ordering vs stencil work) —
this isolates exactly that cost at flagship-like sizes.

Usage: python examples/bench_gather.py [n_nodes_millions [n_rows_millions]]
(defaults 1.9M nodes / 7.4M gathered rows — the 5.67M-dof octree's
finest-level numbers at PCG_TPU_HYBRID_BLOCK=8)
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np



def _sync(y):
    float(jnp.asarray(jax.tree.leaves(y)[0]).ravel()[0])


def timeit(f, *args, reps=10):
    y = f(*args)
    _sync(y)
    t0 = time.perf_counter()
    for _ in range(reps):
        y = f(*args)
    _sync(y)
    return (time.perf_counter() - t0) / reps * 1e3


def main():
    jax.config.update("jax_enable_x64", True)   # the cumsum-diff variant
    n_nodes = int(float(sys.argv[1]) * 1e6) if len(sys.argv) > 1 else 1_900_000
    n_rows = int(float(sys.argv[2]) * 1e6) if len(sys.argv) > 2 else 7_400_000
    rng = np.random.default_rng(0)
    # ~70% of lattice points resolve to real nodes, the rest to the pad
    # row (holes/non-local) — matches the blocked flagship's fill
    idx = rng.integers(0, n_nodes, size=n_rows).astype(np.int32)
    idx[rng.random(n_rows) < 0.3] = n_nodes
    x3p = jnp.asarray(rng.standard_normal((n_nodes + 1, 3)), jnp.float32)
    idxd = jnp.asarray(idx)
    rows = jnp.asarray(rng.standard_normal((n_rows, 3)), jnp.float32)
    y0 = jnp.zeros((n_nodes, 3), jnp.float32)
    print(f"{n_nodes/1e6:.2f}M nodes, {n_rows/1e6:.2f}M rows on "
          f"{jax.devices()[0]}", flush=True)

    gather = jax.jit(lambda t, i: jnp.take(t, i, axis=0, mode="clip"))
    t = timeit(gather, x3p, idxd)
    print(f"row gather:  {t:8.3f} ms  ({t*1e6/n_rows:6.1f} ns/row, "
          f"{n_rows*12/t/1e6:7.1f} GB/s effective)", flush=True)

    scatter = jax.jit(lambda y, i, r: y.at[i].add(r, mode="drop"))
    t = timeit(scatter, y0, idxd, rows)
    print(f"row scatter: {t:8.3f} ms  ({t*1e6/n_rows:6.1f} ns/row)",
          flush=True)

    # --- combine-step alternatives (2026-07-30 session measured the
    # duplicate scatter at 88.7 ns/row vs 5.9 gather — these decide the
    # hybrid backend's scatter-free redesign) -------------------------

    # (a) scatter with SORTED indices (host-side pre-sort is free at
    # partition time; rows arrive pre-permuted)
    idx_sorted = jnp.asarray(np.sort(idx))
    t = timeit(jax.jit(lambda y, i, r: y.at[i].add(
        r, mode="drop", indices_are_sorted=True)), y0, idx_sorted, rows)
    print(f"row scatter sorted:        {t:8.3f} ms  ({t*1e6/n_rows:6.1f} "
          "ns/row)", flush=True)

    # (b) UNIQUE+sorted scatter (one slot per node — what a block-face
    # fold pass would leave behind)
    n_uniq = min(n_nodes, n_rows)
    uidx = jnp.asarray(np.arange(n_uniq, dtype=np.int32))
    urows = rows[:n_uniq]
    t = timeit(jax.jit(lambda y, i, r: y.at[i].add(
        r, mode="drop", indices_are_sorted=True, unique_indices=True)),
        y0, uidx, urows)
    print(f"row scatter unique+sorted: {t:8.3f} ms  ({t*1e6/n_uniq:6.1f} "
          "ns/row, {:.2f}M rows)".format(n_uniq / 1e6), flush=True)

    # (c) gather-transpose combine: rows pre-sorted by target node; each
    # node sums a run of <= K slots via K masked gathers (start/len built
    # at partition time).  Modeled here with the measured fill's run
    # structure from the random idx.
    order = np.argsort(idx, kind="stable")
    sidx = idx[order]
    starts = np.searchsorted(sidx, np.arange(n_nodes, dtype=np.int64))
    lens = np.diff(np.append(starts, len(sidx)))
    K = 2
    gidx = np.minimum(starts[:, None] + np.arange(K)[None],
                      len(sidx) - 1).astype(np.int32)
    gmask = (np.arange(K)[None] < np.minimum(lens, K)[:, None])
    gidx_d, gmask_d = jnp.asarray(gidx), jnp.asarray(gmask[..., None],
                                                     jnp.float32)
    rows_sorted = jnp.asarray(np.asarray(rows)[order])

    def combine_k(rs, gi, gm):
        acc = None
        for k in range(K):
            t_ = jnp.take(rs, gi[:, k], axis=0) * gm[:, k]
            acc = t_ if acc is None else acc + t_
        return acc
    t = timeit(jax.jit(combine_k), rows_sorted, gidx_d, gmask_d)
    cov = float((lens <= K).mean())
    print(f"gather-combine K={K}:        {t:8.3f} ms  (covers {cov*100:.0f}% "
          "of nodes; + residual scatter for the rest)", flush=True)

    # (d) cumsum-difference segmented sum (exact run lengths, any K):
    # f64 prefix over sorted rows + two boundary gathers
    ends = jnp.asarray((starts + lens - 1).astype(np.int32))
    starts_d = jnp.asarray(starts.astype(np.int32))
    has = jnp.asarray((lens > 0)[:, None].astype(np.float32))

    def cumsum_diff(rs, e, s0, h):
        cs = jnp.cumsum(rs.astype(jnp.float64), axis=0)
        hi = jnp.take(cs, e, axis=0)
        lo = jnp.where((s0 == 0)[:, None], 0.0,
                       jnp.take(cs, jnp.maximum(s0 - 1, 0), axis=0))
        return ((hi - lo) * h).astype(jnp.float32)
    t = timeit(jax.jit(cumsum_diff), rows_sorted, ends, starts_d, has)
    print(f"cumsum-diff combine:       {t:8.3f} ms  (exact, f64 prefix)",
          flush=True)

    # reference point: a dense copy of the same byte volume
    big = jnp.asarray(rng.standard_normal((n_rows, 3)), jnp.float32)
    t = timeit(jax.jit(lambda a: a * 1.0000001), big)
    print(f"dense same-bytes pass: {t:8.3f} ms", flush=True)


if __name__ == "__main__":
    main()
