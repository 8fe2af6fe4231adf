"""``progtrace.py`` by hand on made-up events, and against
``recorded_pr26.xplane.pb``: the last 9.14 s of the traced window of PR 26's
chip run (``criteo_share.train``, seed in PERF.md section 5), two iterations
and the stop check, cut with ``python3 benchmark/progtrace.py cut <in> <out>
10 12``.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import progtrace as pt
import tracereduce as tr

RECORDED = HERE / "recorded_pr26.xplane.pb"
KERNELS = {"hist": ["wave_histogram_pallas", "fused_partition_histogram_pallas"]}


def _xspace(planes):
    """{plane: {line: [(name, start_ns, end_ns)]}} -> ProfileData, written the
    way ``cut_trace.py`` writes one."""
    from jax.profiler import ProfileData
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names: dict[str, int] = {}
        body = []
        for lid, (lname, events) in enumerate(lines.items(), 1):
            rows = "\n".join(
                f"    events {{ metadata_id: {names.setdefault(n, len(names) + 1)} "
                f"offset_ps: {int(s * 1000)} duration_ps: {int((e - s) * 1000)} }}"
                for n, s, e in events)
            body.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
                        f"{rows}\n  }}")
        meta = "\n".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                         f'"{n}" }} }}' for n, i in names.items())
        out.append(f'planes {{\n  id: {pid} name: "{pname}"\n'
                   + "\n".join(body) + "\n" + meta + "\n}")
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace("\n".join(out)))


# two iterations of 1000 ns; the second has a stop check. Device: a root pass
# and two wave passes an iteration, a small fusion after each pass, one gap
# while the host prepares iteration 2 and one inside its stop check.
HOST = [
    ("bench/window", 0, 2000),
    ("bench/update_issue", 5, 400),
    ("lgbm/train/iteration", 10, 390),
    ("lgbm/train/prepare", 20, 60),
    ("lgbm/train/step_dispatch", 60, 360),
    ("lgbm/train/record", 365, 380),
    ("bench/update_issue+stop_check", 1000, 1900),
    ("lgbm/train/iteration", 1010, 1890),
    ("lgbm/train/prepare", 1020, 1050),
    ("lgbm/train/step_dispatch", 1050, 1350),
    ("lgbm/train/record", 1355, 1365),
    ("lgbm/train/stop_check", 1370, 1880),
    # before the window: not this window's
    ("lgbm/train/iteration", -500, -100),
]
WORKER = [("lgbm/ingest/prep_chunk", 100, 300)]


def _op(name):
    """A device event's name as the v5e writes it: the whole HLO text."""
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop"


DEVICE = [
    (_op("fusion.1"), 0, 50),
    (_op("wave_histogram_pallas.3"), 50, 250),
    (_op("fusion.2"), 250, 260),
    (_op("while.9"), 260, 900),
    (_op("fused_partition_histogram_pallas.8"), 270, 500),
    (_op("fusion.4"), 500, 520),
    (_op("fused_partition_histogram_pallas.8"), 560, 800),
    (_op("fusion.4"), 800, 830),
    (_op("fusion.7"), 900, 1030),
    # iteration 2 starts late: 1030..1040 idle while the host prepares
    (_op("fusion.1"), 1040, 1100),
    (_op("wave_histogram_pallas.3"), 1100, 1300),
    (_op("fusion.2"), 1300, 1310),
    (_op("fused_partition_histogram_pallas.8"), 1310, 1500),
    (_op("fusion.7"), 1500, 1600),
    # 1600..2000 idle: began inside the stop check
]


@pytest.fixture(scope="module")
def made_up():
    pd = _xspace({"/device:TPU:0": {"XLA Ops": DEVICE, "Steps": [("7", 0, 2000)]},
                  "/host:CPU": {"python3": HOST, "ingest-prefetch_0": WORKER}})
    return pd, pt.reduce(pd, KERNELS)


def test_nest_gives_parent_and_self_time():
    ev = [("a", 0.0, 100.0), ("b", 10.0, 30.0), ("c", 30.0, 90.0),
          ("d", 40.0, 50.0), ("e", 120.0, 130.0)]
    got = {n: (p, s) for n, _, _, p, s in pt.nest(ev)}
    assert got == {"a": (None, 20.0), "b": (0, 20.0), "c": (0, 50.0),
                   "d": (2, 10.0), "e": (None, 10.0)}
    # the same self times as tracereduce charges device operations
    assert sorted(s for _, s in got.values()) == \
        sorted(t for _, t in tr.self_times(ev))


def test_spans_in_the_window_with_parent_and_self(made_up):
    _, red = made_up
    its = [s for s in red["spans"] if s["name"] == "train/iteration"]
    assert [(s["start"], s["end"]) for s in its] == [(10, 390), (1010, 1890)]
    assert [s["self"] for s in its] == [380 - 40 - 300 - 15, 880 - 30 - 300 - 10 - 510]
    kids = {s["name"] for s in red["spans"] if s["parent"] == "train/iteration"}
    assert kids == {"train/prepare", "train/step_dispatch", "train/record",
                    "train/stop_check"}
    # the worker's span is on a line of its own and has no parent there
    (prep,) = [s for s in red["spans"] if s["name"] == "ingest/prep_chunk"]
    assert prep["parent"] is None and prep["thread"] == "ingest-prefetch_0"
    m = red["span_means"]
    assert m["train/step_dispatch"] == {"n": 2, "mean_ms": 300e-6,
                                        "self_mean_ms": 300e-6}
    assert m["train/iteration"]["n"] == 2
    assert m["train/iteration"]["self_mean_ms"] == pytest.approx(27.5e-6)
    assert m["train/stop_check"]["mean_ms"] == pytest.approx(510e-6)


def test_launches_by_name_and_group(made_up):
    _, red = made_up
    assert red["launches"]["wave_histogram_pallas.3"] == 2
    assert red["launches"]["fused_partition_histogram_pallas.8"] == 3
    assert red["launches"]["while.9"] == 1
    assert red["group_launches"] == {"hist": 5}
    # launches x time a launch = the group's device time, as tracereduce has it
    hist_s = tr.reduce(made_up[0], KERNELS)["kernel_s"]["hist"]
    assert hist_s == pytest.approx((200 + 230 + 240 + 200 + 190) * 1e-9)


def test_idle_goes_to_the_innermost_program_span(made_up):
    _, red = made_up
    assert [(k, round(v * 1e9, 6)) for k, v in red["idle_by_span"]] == \
        [("train/stop_check", 400), ("train/prepare", 10)]
    spans = [{"name": "a", "start": 0, "end": 100},
             {"name": "b", "start": 10, "end": 50}]
    assert pt.attribute((20, 30), spans) == "b"
    assert pt.attribute((60, 70), spans) == "a"
    assert pt.attribute((200, 210), spans) == "nothing"


def test_a_program_without_spans_reads_as_nothing():
    """The parent of the PR that brought this file: no ``lgbm/`` span.
    Launches are still counted (they need only the kernels' names); the span
    readers find nothing."""
    pd = _xspace({"/device:TPU:0": {"XLA Ops": DEVICE},
                  "/host:CPU": {"python3": [e for e in HOST
                                            if e[0].startswith("bench/")]}})
    red = pt.reduce(pd, KERNELS)
    assert red["spans"] == [] and red["span_means"] == {}
    assert red["group_launches"] == {"hist": 5}
    assert [k for k, _ in red["idle_by_span"]] == ["nothing"]
    # no traced window at all
    assert pt.reduce(_xspace({"/host:CPU": {"python3": [("x", 0, 1)]}}))["planes"] == 0


def test_readers_return_none_without_a_trace(monkeypatch, tmp_path):
    import importlib.util
    monkeypatch.setattr(pt, "TRACE_ROOT", tmp_path)
    facts = {"done": 2, "trace": {"kernel_s": {}}}
    assert pt.of(facts) is None
    for name in ("entry.dispatch_ms_per_iter", "entry.self_ms_per_iter",
                 "step.passes_per_iter", "kernel.ms_per_pass"):
        spec = importlib.util.spec_from_file_location(
            "r", HERE.parent / "readers" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read(facts) is None, name


def test_registry_readers_tell_absent_from_zero():
    from lightgbm_tpu.obs import registry as obs
    assert pt.registry_timer("unit/never_recorded") is None
    obs.timer("unit/recorded_zero").add(0.0)
    assert pt.registry_timer("unit/recorded_zero") == 0.0
    assert pt.registry_gauge("mem/peak_bytes@unit/never") is None
    obs.gauge("mem/peak_bytes@unit/set").set(3 * 2**30)
    assert pt.registry_gauge("mem/peak_bytes@unit/set") == 3 * 2**30


# -- against the recording ----------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    pd = tr.load(RECORDED)
    return pd, pt.reduce(pd, KERNELS), tr.reduce(pd, KERNELS)


def _device_events(pd):
    (plane,) = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    (line,) = [l for l in plane.lines if l.name == tr.OPS_LINE]
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def test_recorded_window_is_the_last_9_seconds_with_the_stop_check(recorded):
    pd, red, _ = recorded
    (win,) = [(s, e) for n, s, e in tr.host_spans(pd) if n == tr.WINDOW_SPAN]
    assert (win[1] - win[0]) / 1e9 == pytest.approx(9.142167466, abs=1e-9)
    m = red["span_means"]
    assert {k: v["n"] for k, v in m.items()} == {
        "train/iteration": 1, "train/prepare": 1, "train/step_dispatch": 1,
        "train/record": 1, "train/stop_check": 1}
    # the iteration that reads the stop flags back waits two device iterations
    assert m["train/stop_check"]["mean_ms"] == pytest.approx(8453.15877)
    assert m["train/step_dispatch"]["mean_ms"] == pytest.approx(0.97703)
    kids = sum(m[k]["mean_ms"] for k in m if k != "train/iteration")
    assert m["train/iteration"]["mean_ms"] - kids == \
        pytest.approx(m["train/iteration"]["self_mean_ms"])
    assert m["train/iteration"]["self_mean_ms"] == pytest.approx(0.30448)


def test_recorded_launches_counted_again_by_hand(recorded):
    pd, red, base = recorded
    names = [tr.short_name(n) for n, _, _ in _device_events(pd)]
    fused = sum(n.startswith("fused_partition_histogram_pallas") for n in names)
    root = sum(n.startswith("wave_histogram_pallas") for n in names)
    assert (fused, root) == (33, 2)
    assert red["group_launches"] == {"hist": 35}
    assert sum(red["launches"].values()) == len(names) == 15943
    # 9.14 s hold 2.16 iterations' passes; a pass costs what the ledger's does
    assert 1e3 * base["kernel_s"]["hist"] / 35 == pytest.approx(259.616, abs=1e-3)
    # ... the first of them is cut by the recording's start
    whole = [e - s for n, s, e in sorted(_device_events(pd), key=lambda x: x[1])
             if tr.short_name(n).startswith("fused_partition")][1:]
    assert 262.0 < 1e-6 * sum(whole) / len(whole) < 264.0


def test_recorded_gaps_go_to_the_stop_check(recorded):
    """The device idles 3.95 ms of these 9.14 s, and all but 15 us of it
    began while the host sat in ``train/stop_check``."""
    pd, red, base = recorded
    idle = dict(red["idle_by_span"])
    assert idle["train/stop_check"] == pytest.approx(0.003953179, abs=1e-9)
    assert sum(idle.values()) == pytest.approx(
        base["window_s"] - base["busy_s"], abs=1e-9)
    # the same gaps as tracereduce puts down to the benchmark's own spans
    assert sum(v for _, v in base["idle_gaps"]) == pytest.approx(sum(idle.values()))


def test_cut_file_still_reads_as_before_through_tracereduce(recorded):
    _, _, base = recorded
    assert "fused_partition_histogram_pallas.8" in base["op_seconds"]
    assert all(" = " not in k for k in base["op_seconds"])
    assert 100.0 * base["kernel_s"]["hist"] / base["busy_s"] == \
        pytest.approx(99.435, abs=1e-3)
