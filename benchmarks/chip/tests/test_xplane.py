"""The trace reduction on hand-built events and on a hand-built xplane."""

import pytest

import xplane

EVENTS = [("fusion.1", 0.0, 100.0), ("fusion.2", 50.0, 100.0),  # overlap: 0..150
          ("custom-call.3", 300.0, 50.0), ("fusion.1", 400.0, 100.0)]


def test_busy_union_counts_overlap_once():
    assert xplane.busy_union_ns(EVENTS) == 150 + 50 + 100
    assert xplane.busy_union_ns([]) == 0


def test_sums_by_name():
    assert xplane.sums_by_name(EVENTS) == {"fusion.1": 200.0, "fusion.2": 100.0, "custom-call.3": 50.0}


def test_reduce_idle_share_and_mean_over_devices():
    out = xplane.reduce({"/device:TPU:0": EVENTS})
    assert out["window_s"] == pytest.approx(500e-9) and out["busy_s"] == pytest.approx(300e-9)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.4)
    two = xplane.reduce({"/device:TPU:0": EVENTS, "/device:TPU:1": [("fusion.1", 0.0, 500.0)]})
    assert two["busy_s"] == pytest.approx(400e-9)
    assert two["by_name_s"]["fusion.1"] == pytest.approx((200 + 500) / 2 * 1e-9)
    assert xplane.reduce({})["busy_s"] == 0.0


def test_idle_gaps_longest_first():
    assert xplane.idle_gaps(EVENTS, top=2) == [(150.0, 150.0), (350.0, 50.0)]


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 300000 duration_ps: 50000 } }
  lines { id: 2 name: "Steps" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 999000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.3" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "python" } } }
"""


def test_device_events_reads_only_the_ops_line_of_device_planes():
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    per_device = xplane.device_events(profile)
    assert list(per_device) == ["/device:TPU:0"]
    names = [(n, d) for n, _, d in per_device["/device:TPU:0"]]
    assert names == [("fusion.1", 100.0), ("custom-call.3", 50.0)]
    out = xplane.reduce(per_device)
    assert out["busy_s"] == pytest.approx(150e-9) and out["window_s"] == pytest.approx(350e-9)
