"""The two readers of the root pass (``readers/kernel.root_ms_per_iter.py``,
``readers/kernel.root_macs.py``): on a made-up trace reduced by
``tracereduce``, and against the program's registry.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import importlib.util
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import tracereduce as tr

KERNELS = {"hist": ["wave_histogram_pallas", "fused_partition_histogram_pallas"]}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "r", HERE.parent / "readers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _trace(device):
    """[(name, start_ns, end_ns)] on one device's ``XLA Ops`` line, inside a
    2,000 ns window, reduced as ``run.measure`` reduces a run's trace."""
    from jax.profiler import ProfileData
    names = {}
    rows = "\n".join(
        f"    events {{ metadata_id: {names.setdefault(n, len(names) + 1)} "
        f"offset_ps: {s * 1000} duration_ps: {(e - s) * 1000} }}"
        for n, s, e in device)
    meta = "\n".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                     f'"{n}" }} }}' for n, i in names.items())
    host = ('planes {\n  id: 2 name: "/host:CPU"\n  lines { id: 1 name: '
            '"python3" timestamp_ns: 0\n    events { metadata_id: 1 offset_ps: '
            '0 duration_ps: 2000000 }\n  }\n  event_metadata { key: 1 value '
            '{ id: 1 name: "bench/window" } }\n}')
    text = (f'planes {{\n  id: 1 name: "/device:TPU:0"\n  lines {{ id: 1 '
            f'name: "XLA Ops" timestamp_ns: 0\n{rows}\n  }}\n{meta}\n}}\n{host}')
    return tr.reduce(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)), KERNELS)


def _op(name):
    """A device event's name as the v5e writes it: the whole HLO text."""
    return f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p)"


def test_root_ms_per_iter_reads_the_root_kernel_alone():
    """Two iterations: a root pass of 200 and of 180 ns, wave passes and a
    fusion beside them; the wave passes' time is not the root's."""
    red = _trace([
        (_op("fusion.1"), 0, 50),
        (_op("wave_histogram_pallas.3"), 50, 250),
        (_op("fused_partition_histogram_pallas.8"), 270, 500),
        (_op("fused_partition_histogram_pallas.8"), 560, 800),
        (_op("wave_histogram_pallas.3"), 1100, 1280),
        (_op("fused_partition_histogram_pallas.8"), 1310, 1500),
    ])
    read = _reader("kernel.root_ms_per_iter")
    assert read({"trace": red, "done": 2}) == pytest.approx(190e-6)
    # what the accepted group metric reads holds the root's share
    assert red["kernel_s"]["hist"] == pytest.approx((380 + 230 + 240 + 190) * 1e-9)
    # nothing to read: no iteration done, no such kernel, no trace facts
    assert read({"trace": red, "done": 0}) is None
    only_waves = _trace([(_op("fused_partition_histogram_pallas.8"), 0, 100)])
    assert read({"trace": only_waves, "done": 2}) is None
    assert read({"trace": {"kernel_s": {}}, "done": 2}) is None


def test_root_macs_reads_the_gauge_and_none_without_it(monkeypatch):
    """A program without the gauge (this PR's parent), or one that has not
    set it yet, reads None; one that set it reads what it set, 0.0 included
    (a root whose dot is not the grower's to price). The gauge is the
    process's: it is put back at the end to what it held."""
    from lightgbm_tpu.obs import registry as obs
    read = _reader("kernel.root_macs")
    gauge = obs.default_registry().gauge("hist/root_macs")
    monkeypatch.setattr(gauge, "_value", None)
    assert read({}) is None
    for macs in (5120.0, 32768.0, 0.0):
        gauge.set(macs)
        assert read({}) == macs
