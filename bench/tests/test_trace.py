"""The trace-to-metric reduction, on a short trace of the yi-6b chat cell
recorded on a TPU v5e chip (``data/small.xplane.pb.gz``, a 0.3 s traced
window), read on the CPU."""
import gzip
import os

import pytest

from harness import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    with gzip.open(DATA, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    return pd, T.reduce(pd)


def test_union_and_total_by_hand():
    iv = [(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)]
    assert T.union(iv) == [(0, 3), (5, 9), (10, 11)]
    assert T.total(T.union(iv)) == 8


def test_short_name_drops_operands():
    name = ("%fusion.9 = bf16[4] fusion(bf16[4] %paged_attention.26), "
            "kind=kLoop")
    assert T.short(name) == "fusion.9"
    assert "paged_attention" not in T.short(name)


def test_device_busy_and_window(reduced):
    _, red = reduced
    assert list(red.devices) == ["/device:TPU:0"]
    assert 0 < red.busy_s <= red.window_s
    idle = 1 - red.busy_s / red.window_s
    assert 0 <= idle < 1


def test_kernels_and_programs_found(reduced):
    pd, red = reduced
    for kernel in ("paged_attention", "spec_verify_wm"):
        assert red.op_count(kernel) > 0
        assert red.op_seconds(kernel) > 0
    # the kernel's time is the sum of its own events, counted by hand
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in plane.lines if ln.name == T.OPS_LINE)
    by_hand = sum(ev.duration_ns for ev in ops.events
                  if T.short(ev.name).startswith("paged_attention"))
    assert red.op_seconds("paged_attention") == pytest.approx(
        by_hand * 1e-9, rel=1e-6)
    assert red.module_seconds("jit_loop") > 0
    assert red.module_seconds("jit_loop") <= red.window_s


def test_breakdown_lists(reduced):
    _, red = reduced
    top = red.top_ops(10)
    assert 0 < len(top) <= 10
    assert not any(n.startswith("while") for n, _ in top)
    assert all(s > 0 for _, s in top)
    gaps = red.idle_gaps(10)
    assert len(gaps) <= 10 and all(s >= 0 for _, s in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                          reverse=True)
