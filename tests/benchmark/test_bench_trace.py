"""The benchmark's trace reduction: interval union, scope buckets,
collectives, idle gaps, and nothing dropped."""

import jax
import jax.numpy as jnp
import pytest

from harness import trace


def test_merge_unions_overlaps_and_drops_empty():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == [
        (0, 3), (5, 9)]


def test_base_name_strips_suffixes():
    assert trace.base_name("fusion.12") == "fusion"
    assert trace.base_name("all-reduce-start.3.clone") == "all-reduce-start"
    assert trace.is_collective("collective-permute-done.1")
    assert not trace.is_collective("fusion.3")


def _synthetic():
    ops = {"TPU:0": [
        # (start, end, name, phase, program)
        (0, 100, "fusion.1", "matvec", "jit__step"),
        (50, 150, "fusion.2", "matvec", "jit__step"),
        (200, 260, "all-reduce.3", "reduce", "jit__step"),
        (300, 340, "fusion.4", "axpy", "jit__step"),
        (400, 450, "copy.5", None, "jit__start"),
        (460, 470, "while.6", None, "jit__step"),
        (900, 1200, "fusion.7", "precond", "jit__step"),
    ]}
    spans = [("window", 0, 1000), ("dispatch", 0, 500), ("fetch", 500, 800)]
    return ops, spans


def test_reduce_buckets_every_op_once_inside_the_window():
    ops, spans = _synthetic()
    r = trace.reduce(ops, spans)
    assert r["window_s"] == pytest.approx(1000e-9)
    ph = {k: v * 1e9 for k, v in r["phase_s"].items()}
    # fusion.7 is clipped at the window's end; while.6 wraps other ops
    assert ph == pytest.approx({"matvec": 200, "precond": 100,
                                "reduce": 60, "axpy": 40})
    assert r["other_s"] * 1e9 == pytest.approx(50)
    assert r["collective_s"] * 1e9 == pytest.approx(60)
    # busy is the union: [0,150) [200,260) [300,340) [400,450) [900,1000)
    assert r["busy_s"] * 1e9 == pytest.approx(150 + 60 + 40 + 50 + 100)
    # nothing dropped: the buckets hold all the clipped op time
    clipped = 100 + 100 + 60 + 40 + 50 + 100
    total = sum(r["phase_s"].values()) + r["other_s"]
    assert total * 1e9 == pytest.approx(clipped)
    # the longest gap [450, 900) is named by the host span open in it
    name, secs = r["idle_gaps"][0]
    assert name == "fetch (TPU:0)" and secs * 1e9 == pytest.approx(450)
    # ops are listed by time under their program, name and phase
    assert r["device_ops"][0] == ["jit__step/fusion.1 [matvec]",
                                  pytest.approx(100e-9)]
    assert ["jit__start/copy.5 [unscoped]", pytest.approx(50e-9)] \
        in r["device_ops"]


def test_phase_of_a_fusion_is_the_scope_it_names_most():
    assert trace.phase_of("jit(_step)/while/body/pcg/matvec/dot") == "matvec"
    assert trace.phase_of("a/pcg/axpy/mul b/pcg/reduce/x c/pcg/axpy/y") \
        == "axpy"
    assert trace.phase_of("jit(_start)/broadcast") is None
    assert trace.phase_of(None) is None


def test_reduce_needs_one_window():
    ops, spans = _synthetic()
    with pytest.raises(RuntimeError, match="window"):
        trace.reduce(ops, [s for s in spans if s[0] != "window"])


def test_reduce_a_recorded_cpu_trace(tmp_path):
    """A real profiler trace of scoped work: every op is read, the union
    stays inside the window, and the buckets hold all op time."""

    @jax.jit
    def f(x):
        def body(i, x):
            with jax.named_scope("pcg/matvec"):
                x = jnp.tanh(x @ x)
            return x
        return jax.lax.fori_loop(0, 20, body, x)

    x = jnp.ones((128, 128)) * 0.01
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, spans = trace.read(str(tmp_path))
    assert [n for n, _, _ in spans].count("window") == 1
    r = trace.reduce(ops, spans)
    assert r["n_ops"] > 0
    # the scope is found through the traced program's HLO
    assert r["scoped_ops"] > 0 and r["phase_s"]["matvec"] > 0
    assert 0 < r["busy_s"] <= r["window_s"]
    clipped = 0
    w = next((s, e) for n, s, e in spans if n == "window")
    for dev in r["devices"]:
        for s, e, name, _, _ in ops[dev]:
            if trace.base_name(name) not in trace.CONTAINERS:
                clipped += max(0, min(e, w[1]) - max(s, w[0]))
    total = sum(r["phase_s"].values()) + r["other_s"]
    assert total * 1e9 * len(r["devices"]) == pytest.approx(clipped)
