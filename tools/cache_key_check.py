"""Persistent-compile-cache KEY-IDENTITY check: chipless vs remote.

A pre-warmed ``.jax_cache`` assumes the chip's backend computes the SAME
cache key for a program as the local chipless topology path
(``tools/aot_compile_check.py``).  If the keys differ, every flagship
attempt still pays the full compile on the chip and the pre-warming
buys nothing.

Two modes, one marker program (a fixed 64-step tanh-matmul scan — small,
a few seconds to compile, structurally unlike any solver program so it
cannot collide with real entries):

  python tools/cache_key_check.py --seed     # chipless: compile the
        marker via the v5e topology path into .jax_cache and record the
        cache-dir manifest (run on the build host, no chip needed)
  python tools/cache_key_check.py            # live session: compile the
        SAME marker on the real backend and report
        CACHE_KEY_MATCH    — no new cache entry appeared (+ fast compile)
        CACHE_KEY_MISMATCH — the remote backend wrote a NEW entry (its
                             key differs; pre-warmed entries are useless
                             remotely — rely on same-session retry
                             caching only and budget flagship steps for
                             cold compiles)

Exit code 0 = match, 4 = mismatch, 1 = error (probe/compile failed).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CACHE_DIR = os.path.join(REPO, ".jax_cache")
MANIFEST = os.path.join(REPO, ".jax_cache_manifest.json")


def _enable_cache():
    """Returns the EFFECTIVE cache dir (a pre-exported
    JAX_COMPILATION_CACHE_DIR wins — _listing must watch the dir entries
    actually land in, not the default)."""
    import jax

    eff = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", eff)
    # the marker compiles in ~1-3 s; without this it may fall under the
    # default 1 s persistence threshold and never be written at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return eff


def _marker_fn(salt):
    """``salt`` (a float folded into the program as a constant) makes each
    SEED's marker a distinct program: a remote compile from an earlier
    seed generation can never be hit by the current check, so a stale
    remotely-keyed entry cannot fake a CACHE_KEY_MATCH."""
    import jax
    import jax.numpy as jnp

    def step(x, _):
        return jnp.tanh(x @ x.T @ x * 0.01 + salt), None

    def fn(x):
        y, _ = jax.lax.scan(step, x, None, length=64)
        return y.sum()

    return fn, (256, 256)


def _listing(cache_dir):
    try:
        return sorted(os.listdir(cache_dir))
    except OSError:
        return []


def seed():
    """Chipless-compile the marker into the persistent cache."""
    cache_dir = _enable_cache()
    import numpy as np
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    before = _listing(cache_dir)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices)[:1], ("x",))
    s = NamedSharding(mesh, PartitionSpec())
    # fresh salt per seed: derived from the wall clock, recorded in the
    # manifest so check() rebuilds the IDENTICAL program
    salt = round(0.1 + (time.time() % 1000.0) / 8000.0, 9)
    fn, shape = _marker_fn(salt)
    t0 = time.perf_counter()
    jax.jit(fn).lower(
        jax.ShapeDtypeStruct(shape, "float32", sharding=s)).compile()
    wall = time.perf_counter() - t0
    after = _listing(cache_dir)
    new = sorted(set(after) - set(before))
    with open(MANIFEST, "w") as f:
        json.dump({"seeded_at_utc": time.strftime(
                       "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                   "salt": salt, "marker_entries": new,
                   "all_entries": after, "compile_s": round(wall, 1)}, f,
                  indent=1)
    print(f"seeded: {len(new)} new cache entr{'y' if len(new)==1 else 'ies'} "
          f"in {wall:.1f}s -> {MANIFEST}", flush=True)
    if not new:
        print("WARNING: the fresh-salted marker produced no cache entry — "
              "the persistent cache is not writing; seeding is not "
              "verifiable", flush=True)
        return 1
    return 0


def check():
    """Live session: compile the marker remotely, compare cache entries."""
    cache_dir = _enable_cache()
    from pcg_mpi_solver_tpu.bench import _probe_with_retry

    ok, detail = _probe_with_retry(budget_s=float(
        os.environ.get("BENCH_PROBE_BUDGET_S", 300)), probe_timeout_s=180)
    if not ok:
        print(f"ERROR: accelerator unreachable ({detail})", flush=True)
        return 1
    import jax

    dev = jax.devices()[0]
    print(f"# backend: {dev.platform} {dev.device_kind}", flush=True)
    try:
        with open(MANIFEST) as f:
            man = json.load(f)
    except (OSError, ValueError):
        print("ERROR: no seed manifest — run "
              "`python tools/cache_key_check.py --seed` on the build host "
              "first", flush=True)
        return 1
    missing = [e for e in man.get("marker_entries", [])
               if e not in _listing(cache_dir)]
    if missing:
        print(f"ERROR: seeded marker entries missing from the cache dir "
              f"({missing}) — .jax_cache was cleared since the seed; "
              "re-seed before checking", flush=True)
        return 1
    before = _listing(cache_dir)
    fn, shape = _marker_fn(man["salt"])
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    # mirror the seed's lowering EXACTLY (ShapeDtypeStruct + 1-device
    # NamedSharding): any difference here would test our own call-site
    # divergence, not the backend's key computation
    s = NamedSharding(Mesh(np.array([dev]), ("x",)), PartitionSpec())
    t0 = time.perf_counter()
    jax.jit(fn).lower(
        jax.ShapeDtypeStruct(shape, "float32", sharding=s)).compile()
    wall = time.perf_counter() - t0
    new = sorted(set(_listing(cache_dir)) - set(before))
    print(f"# marker compile {wall:.1f}s; new cache entries: {new}; "
          f"seeded marker entries: {man.get('marker_entries')}", flush=True)
    if new:
        # drop the remotely-keyed marker entries so a re-run of this
        # check (the queues re-run on session recovery) cannot hit them
        # and report a false MATCH
        for e in new:
            try:
                os.remove(os.path.join(cache_dir, e))
            except OSError:
                pass
        print("CACHE_KEY_MISMATCH: the remote backend keyed the marker "
              "differently from the chipless seed — pre-warmed .jax_cache "
              "entries will NOT be hit; budget flagship steps for cold "
              "compiles (same-session retries still hit the entries this "
              "session writes)", flush=True)
        return 4
    # 'no new entry' only means MATCH if this backend's cache WRITES are
    # actually landing where we look — prove it with a second,
    # never-seeded probe program (salt+1).  A silently-failing write
    # (full disk, unwritable dir, redirected path) would otherwise fake
    # the exact 'pre-warming works' verdict this tool exists to refute.
    probe_fn, shape = _marker_fn(man["salt"] + 1.0)
    before2 = _listing(cache_dir)
    jax.jit(probe_fn).lower(
        jax.ShapeDtypeStruct(shape, "float32", sharding=s)).compile()
    probe_new = sorted(set(_listing(cache_dir)) - set(before2))
    for e in probe_new:
        try:
            os.remove(os.path.join(cache_dir, e))
        except OSError:
            pass
    if not probe_new:
        print("CACHE_WRITE_BROKEN: the unseeded probe compile produced no "
              "cache entry — writes are not landing in "
              f"{cache_dir}; the marker's apparent hit proves nothing. "
              "Treat pre-warmed entries as absent.", flush=True)
        return 1
    print("CACHE_KEY_MATCH: remote compile hit the chipless-seeded entry "
          "(and cache writes verified live) — pre-warmed flagship "
          "programs should load in seconds", flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", action="store_true")
    args = ap.parse_args()
    sys.exit(seed() if args.seed else check())


if __name__ == "__main__":
    main()
