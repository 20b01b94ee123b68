"""The reading of the program's own trace labels (harness/spans.py) on
recorded traces of rehearsal-size cube and octree solves: it keeps
every key and metric the benchmark's reduction (harness/trace.py)
computes from the same trace, its stage and scope splits sum exactly to
the phase they split, and every idle nanosecond of the window is
charged to exactly one program span or to the host."""

import time

import pytest

from harness import cell, spans, trace
from harness.spec import Spec


@pytest.fixture(scope="module", params=["cube.single", "octree.single"])
def traced(request, tmp_path_factory):
    """One traced rehearsal run of the cell, its kept trace, and the run
    facts the benchmark's metric readers were given."""
    keep = str(tmp_path_factory.mktemp(request.param.replace(".", "_")))
    facts = []
    orig = Spec.reader

    def reader(self, name):
        read = orig(self, name)

        def spy(run):
            facts.append(run)
            return read(run)
        return spy

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Spec, "reader", reader)
        result, _ = cell.run(request.param, 2 ** 31 + 23, 0.0, True,
                             time.perf_counter(), rehearsal=True,
                             keep_trace=keep)
    ops, sp, launches = spans.read(keep)
    return dict(cell=request.param, result=result, keep=keep,
                facts=facts[-1], red=spans.reduce(ops, sp, launches),
                spans=sp)


def test_existing_keys_and_metrics_are_unchanged(traced):
    """Every key of the benchmark's reduction, and every per-layer
    metric read from it, is the same read through spans.reduce; the idle
    gaps keep their durations and only change their names."""
    old = trace.reduce(*trace.read(traced["keep"]))
    assert old == traced["facts"]["trace"]
    new = traced["red"]
    for key, value in old.items():
        if key == "idle_gaps":
            assert [g for _, g in new[key]] == [g for _, g in value]
        elif key == "per_device":
            # each device's figures gain the new ones beside the old
            for dev, d in value.items():
                assert {k: new[key][dev][k] for k in d} == d, dev
        else:
            assert new[key] == value, key
    spec = Spec()
    metrics = traced["result"]["metrics"]
    for entry, read in spec.metrics(traced["cell"], True):
        if entry["name"] not in metrics:
            continue
        assert read(dict(traced["facts"], trace=new)) \
            == metrics[entry["name"]]["value"], entry["name"]


def test_stage_and_scope_splits_sum_exactly(traced):
    red = traced["red"]
    for dev, d in red["per_device"].items():
        assert sum(d["matvec_stage_ns"].values()) == d["phase_ns"]["matvec"]
        assert sum(d["solve_scope_ns"].values()) == d["other_ns"]
    assert sum(red["matvec_stage_s"].values()) == pytest.approx(
        red["phase_s"]["matvec"], rel=1e-12)
    assert sum(red["solve_scope_s"].values()) == pytest.approx(
        red["other_s"], rel=1e-12)
    # the labels reach the trace: no matvec time is left unstaged, and
    # the solve scopes name at least nine tenths of the rest
    st = red["matvec_stage_s"]
    assert st["contract"] > 0 and st["scatter"] > 0
    assert st["unstaged"] <= 0.05 * red["phase_s"]["matvec"]
    assert red["solve_scope_s"]["none"] <= 0.1 * red["other_s"]
    assert red["solve_scope_s"]["residual64"] > 0
    assert red["solve_scope_s"]["track"] > 0


def test_every_idle_nanosecond_has_one_owner(traced):
    red = traced["red"]
    (w0, w1), = [(s, e) for n, s, e, _ in traced["spans"] if n == "window"]
    for dev, d in red["per_device"].items():
        assert d["idle_ns"] + d["busy_ns"] == w1 - w0
        assert sum(d["span_idle_ns"].values()) == d["idle_ns"]
        for name, ns in d["span_idle_total_ns"].items():
            assert d["span_idle_ns"][name] <= ns
    idle = sum(s["idle_s"] for s in red["spans"].values())
    assert idle + red["host_idle_s"] == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    for s in red["spans"].values():
        assert 0 <= s["self_s"] <= s["total_s"]
        assert 0 <= s["idle_s"] <= s["idle_total_s"] <= s["total_s"]


def test_program_spans_name_the_idle_gaps(traced):
    """Inside the request the gaps are named by the program's spans,
    never by the benchmark's ``dispatch``; the solve span carries the
    step number and nests the request's dispatches."""
    red = traced["red"]
    assert not any(n.startswith("dispatch (") for n, _ in red["idle_gaps"])
    solves = [(s, e, a) for n, s, e, a in traced["spans"]
              if n == spans.SOLVE_SPAN]
    assert len(solves) == 1 and solves[0][2].get("step")
    sp = red["spans"]
    for name in ("reset", "fetch.transfer", "fetch.assemble"):
        assert sp[f"pcg-tpu/{name}"]["count"] == 1
    inside = "pcg-tpu/step" if traced["cell"] == "cube.single" \
        else "pcg-tpu/inner_cycle"
    assert sp[inside]["count"] >= 1
    assert sp[spans.SOLVE_SPAN]["self_s"] < sp[spans.SOLVE_SPAN]["total_s"]
    if traced["cell"] == "octree.single":
        assert sp["pcg-tpu/refine_cycle"]["count"] >= 1
    assert red["programs"] >= 1


def test_per_layer_readings(traced):
    iters = sum(r["iters"] for r in traced["facts"]["traced_solves"])
    got = spans.metrics(traced["red"], iters, 1)
    assert len(got) == 11
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["matvec.contract_ms_per_iter"] > 0
    assert got["dispatch.programs_per_solve"] >= 1


def test_label_of_takes_the_innermost_then_the_most_named():
    pat = spans._SOLVE
    assert spans.label_of(pat, "jit(f)/solve/start/while/body/solve/track/"
                               "add") == "track"
    assert spans.label_of(pat, "a/solve/start/x b/solve/residual64/y "
                               "c/solve/residual64/z") == "residual64"
    assert spans.label_of(pat, "jit(f)/pcg/matvec/add") is None
    assert spans.label_of(pat, None) is None
    # an op that names no scope takes its computation's; one that names
    # its own keeps it
    assert spans._labels("", "", "track") == (None, None, "track")
    assert spans._labels("copy-done.3", "", "track") == (None, None, "track")
    assert spans._labels("", "j/solve/start/pcg/matvec/mv/halo/c", "track") \
        == ("matvec", "halo", "start")
