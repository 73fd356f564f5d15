"""The trace reduction on a small recorded trace."""
import pytest
from jax.profiler import ProfileData

import tracefile

TRACE = '''
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 6000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.observe" } }
  event_metadata { key: 4 value { id: 4 name: "other" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1500000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 2500000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 10500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_run" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[8] fusion(f32[8] %p)" } }
  event_metadata { key: 4 value { id: 4 name: "%while.1 = (s32[]) while((s32[]) %t)" } }
}
'''


def test_reduce_small_trace():
    r = tracefile.reduce(ProfileData.from_text_proto(TRACE))
    assert r["window_s"] == 10e-6                   # the bench.traced span
    # ops: a while [1.5,4.5] holding [1.5,2.5] and [2.5,4.5]; [5,7];
    # [10.5,11 (clipped)] us
    assert abs(r["busy_s"] - (3.0 + 2.0 + 0.5) * 1e-6) < 1e-12
    assert r["n_devices"] == 1
    assert r["modules"]["jit_run"] == pytest.approx([3e-6, 2e-6])
    # leaf ops only (the while encloses two), named by their instruction
    ops = dict(r["device_ops"])
    assert set(ops) == {"fusion.1", "%fusion.2"}
    assert ops["fusion.1"] == pytest.approx(3e-6)
    assert ops["%fusion.2"] == pytest.approx(2.5e-6)
    # idle [1,1.5] in bench.step, [4.5,5] in bench.step, [7,10.5] in
    # bench.observe; the host span outside the window is not a phase
    gaps = r["idle_gaps"]
    assert gaps[0][0] == "bench.observe" and abs(gaps[0][1] - 3.5e-6) < 1e-12
    assert sorted(g[0] for g in gaps[1:]) == ["bench.step", "bench.step"]
    assert abs(sum(g[1] for g in gaps) - (10 - 5.5) * 1e-6) < 1e-12


def test_no_window_span_is_an_error():
    bad = TRACE.replace('name: "bench.traced"', 'name: "bench.other"')
    with pytest.raises(ValueError):
        tracefile.reduce(ProfileData.from_text_proto(bad))
