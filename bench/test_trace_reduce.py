"""The trace reduction against a trace whose answers are known: a small
XSpace written out by hand (planes, lines and events as a TPU trace lays
them out) and serialised by JAX's own profiler."""
from __future__ import annotations

import numpy as np
import pytest
from jax.profiler import ProfileData

import trace_reduce as tr

MS = 10 ** 9          # picoseconds per millisecond

# device ops in ms from the line's start: two overlap, so busy is
# [0, 4) + [6, 7) + [8.5, 9) = 5.5 ms of a 10 ms window
OPS = [("fusion.1", 0.0, 2.0), ("dot.2", 1.0, 3.0), ("fusion.1", 6.0, 1.0),
       ("copy.3", 8.5, 0.5)]
SPANS = [("bench.window", 0.0, 10.0, None), ("bench.reprice", 0.5, 4.0, 1),
         ("bench.publish", 5.5, 2.0, None), ("bench.feed_wait", 7.5, 2.0,
                                             None)]


def _xspace() -> bytes:
    op_ids = {n: i + 1 for i, n in enumerate(dict.fromkeys(n for n, *_ in OPS))}
    span_ids = {n: i + 1 for i, (n, *_) in enumerate(SPANS)}
    ops = "".join(
        f"events {{ metadata_id: {op_ids[n]} offset_ps: {int(s * MS)} "
        f"duration_ps: {int(d * MS)} }}\n" for n, s, d in OPS)
    spans = "".join(
        f"events {{ metadata_id: {span_ids[n]} offset_ps: {int(s * MS)} "
        f"duration_ps: {int(d * MS)} "
        + (f"stats {{ metadata_id: 1 int64_value: {e} }} " if e else "")
        + "}\n" for n, s, d, e in SPANS)
    meta = lambda ids: "".join(  # noqa: E731
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in ids.items())
    text = (
        'planes { id: 1 name: "/device:TPU:0"\n'
        '  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000000\n'
        f'    events {{ metadata_id: 99 offset_ps: 0 duration_ps: {10 * MS} }} }}\n'
        f'  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000000\n{ops} }}\n'
        f'{meta(op_ids)}'
        '  event_metadata { key: 99 value { id: 99 name: "jit_step" } } }\n'
        'planes { id: 2 name: "/host:CPU"\n'
        f'  lines {{ id: 7 name: "python3" timestamp_ns: 1000000\n{spans} }}\n'
        f'{meta(span_ids)}'
        '  stat_metadata { key: 1 value { id: 1 name: "epoch" } } }\n')
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "plugins" / "vm.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_xspace())
    return tr.reduce_dir(str(path.parent.parent))


def test_busy_idle_and_window(reduced):
    trace, s = reduced
    # only the XLA Ops line counts: the module line spans the whole window
    assert s.window_s == pytest.approx(10e-3)
    assert s.busy_s == pytest.approx(5.5e-3)
    assert s.idle_share == pytest.approx(0.45)


def test_device_time_inside_each_span(reduced):
    trace, s = reduced
    assert s.in_spans["bench.reprice"] == (1, pytest.approx(3.5e-3))
    assert s.in_spans["bench.publish"] == (1, pytest.approx(1.0e-3))
    assert s.in_spans["bench.feed_wait"] == (1, pytest.approx(0.5e-3))
    (reprice,) = tr.spans_named(trace, "bench.reprice")
    assert reprice.stats["epoch"] == 1
    assert tr.device_in(trace, reprice) == pytest.approx(3.5e-3)


def test_ops_and_idle_gaps_by_host_activity(reduced):
    trace, s = reduced
    ops = dict((k, v) for k, v in s.device_ops)
    assert ops == pytest.approx({"fusion.1": 3e-3, "dot.2": 3e-3,
                                 "copy.3": 0.5e-3})
    gaps = dict((k, v) for k, v in s.idle_gaps)
    # [4, 6) and [9, 10) fall outside every span; [7, 8.5) in the wait
    assert gaps == pytest.approx({"outside every benchmark span": 3e-3,
                                  "bench.feed_wait": 1.5e-3})


def test_union_and_covered():
    iv = np.array([[5, 9], [0, 2], [1, 3], [8, 12], [20, 21]])
    u = tr.union(iv)
    assert u.tolist() == [[0, 3], [5, 12], [20, 21]]
    assert tr.covered(u, 0, 30) == 3 + 7 + 1
    assert tr.covered(u, 2, 6) == 1 + 1
    assert tr.covered(u, 12, 20) == 0
    assert tr.covered(u, 6, 6) == 0


def test_a_device_plane_without_its_ops_line_is_an_error(tmp_path):
    text = ('planes { id: 1 name: "/device:TPU:0"\n'
            '  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000000\n'
            f'    events {{ metadata_id: 99 offset_ps: 0 duration_ps: {MS} }} }}\n'
            '  event_metadata { key: 99 value { id: 99 name: "jit_step" } } }\n')
    path = tmp_path / "vm.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    with pytest.raises(ValueError, match="XLA Ops"):
        tr.load(str(path))
