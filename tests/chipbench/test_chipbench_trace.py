"""The benchmark's trace reduction: device busy time and idle gaps, device
time by layer pattern, idle gaps by host span, and one small trace
recorded on the CPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))

from chipbench import trace  # noqa: E402

LAYERS = {"decode": ["ar_segment_scan"], "simulator": ["simulate_batch"],
          "update": ["tf_segment", "adam"]}


def test_union_merges_overlaps_and_clips_to_the_window():
    ivs = [(0.0, 1.0, "a"), (0.5, 2.0, "b"), (3.0, 4.0, "c"),
           (9.0, 11.0, "d")]
    assert trace.union(ivs, 0.5, 10.0) == [(0.5, 2.0), (3.0, 4.0),
                                          (9.0, 10.0)]
    busy = trace.union(ivs, 0.0, 10.0)
    assert trace.gaps(busy, 0.0, 10.0) == [(2.0, 3.0), (4.0, 9.0)]


def test_device_time_by_layer_pattern():
    ivs = [(0.0, 1.0, "jit__ar_segment_scan"), (1.0, 1.5, "jit__tf_segment"),
           (1.5, 2.0, "jit_adam_update"), (2.0, 2.25, "jit__simulate_batch_jit"),
           (2.25, 2.5, "jit_concatenate")]
    got = trace.by_layer(ivs, LAYERS, 0.0, 10.0)
    assert got == {"decode": 1.0, "update": 1.0, "simulator": 0.25,
                   "other": 0.25}
    assert trace.top_programs(ivs, 0.0, 10.0, k=2) == [
        ["jit__ar_segment_scan", 1.0], ["jit__tf_segment", 0.5]]


def test_module_names_lose_the_run_suffix():
    assert trace.module_name("jit__tf_segment(1234)") == "jit__tf_segment"
    assert trace.module_name("jit_f") == "jit_f"


def test_idle_gaps_go_to_the_innermost_open_span():
    spans = [(0.0, 10.0, "ppo.iteration"), (1.0, 4.0, "ppo.sample"),
             (2.0, 3.0, "placer.ar_segment"), (6.0, 9.0, "ppo.update")]
    idle = [(2.2, 2.4), (3.5, 3.7), (5.0, 5.5), (7.0, 8.0), (11.0, 12.0)]
    got = dict((n, round(t, 6)) for n, t in trace.attribute(idle, spans))
    assert got == {"placer.ar_segment": 0.2, "ppo.sample": 0.2,
                   "ppo.iteration": 0.5, "ppo.update": 1.0, "host": 1.0}


def test_reduce_averages_devices_and_shifts_host_spans():
    raw = {"host": [(100.0, 110.0, trace.WINDOW),
                    (101.0, 102.0, "chipbench.request")],
           "devices": {"/device:TPU:0": [(100.0, 105.0, "jit__tf_segment")],
                       "/device:TPU:1": [(100.0, 103.0, "jit__tf_segment")]}}
    spans = [(50.0, 60.0, "ppo.update")]      # host clock: window opened at 45
    red = trace.reduce(raw, LAYERS, spans, clock_start=45.0)
    assert red["window_s"] == 10.0
    assert red["busy_s"] == 4.0
    assert red["layer_s"] == {"update": 4.0}
    assert red["idle_gaps"] == [["ppo.update", 5.0]]


def test_reduce_refuses_a_trace_without_its_window():
    with pytest.raises(ValueError):
        trace.reduce({"host": [], "devices": {}}, LAYERS)


def test_a_trace_recorded_on_the_cpu():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with trace.Capture() as cap:
        for _ in range(3):
            f(x).block_until_ready()
    assert not os.path.exists(cap.dir)
    red = trace.reduce(cap.raw, {"mine": ["lambda"]}, [],
                       cap.clock_start)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["layer_s"].get("mine", 0.0) > 0
    assert red["device_ops"][0][0].startswith("jit_")
