"""Program objects built from a configuration file, shared by the drivers.

Every value the program is given comes from the file: the policy's
widths and switches, the PPO settings, the fleet.  Nothing is read from
the program's own presets, so a later change to them cannot move a cell.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np


def graph(spec: Dict[str, Any]):
    """The dataflow graph a ``{"family", "kwargs"}`` entry names, made by
    the program's own generator."""
    from repro.graphs import synthetic
    return getattr(synthetic, spec["family"])(**spec["kwargs"])


def mem_caps(fleet: Dict[str, Any], g) -> list:
    """Per-device memory caps by the fleet's rule: the device's own memory
    (``device``), or a slack times an even share of the graph's resident
    bytes (``share``)."""
    d = int(fleet["num_devices"])
    rule = fleet["mem_cap_rule"]
    if rule == "device":
        return [float(fleet["device_mem_bytes"])] * d
    if rule == "share":
        return [g.total_mem() / d * float(fleet["mem_cap_slack"])] * d
    raise ValueError(f"unknown mem_cap_rule {rule!r}")


def topology(fleet: Dict[str, Any], caps):
    from repro.sim.device import DeviceSpec, Topology
    spec = DeviceSpec(fleet["device"], peak_flops=float(fleet["peak_flops"]),
                      mem_bytes=float(fleet["device_mem_bytes"]),
                      hbm_bw=float(fleet["hbm_bw"]))
    return Topology.uniform(int(fleet["num_devices"]), spec,
                            link_bw=float(fleet["link_bw"]),
                            link_latency=float(fleet["link_latency_s"])
                            ).with_mem_caps(np.asarray(caps, np.float64))


def reference_fleet(fleet: Dict[str, Any], caps) -> Dict[str, Any]:
    return dict(fleet, mem_caps=list(caps))


def policy_config(p: Dict[str, Any]):
    from repro.core.policy import PolicyConfig
    from repro.core.scale import ScaleConfig
    return PolicyConfig(
        hidden=p["hidden"], gnn_layers=p["gnn_layers"], op_emb=p["op_emb"],
        placer_layers=p["placer_layers"], heads=p["heads"], ffn=p["ffn"],
        window=p["window"], max_devices=p["max_devices"],
        use_attention=p["use_attention"],
        use_superposition=p["use_superposition"], agg_impl=p["agg_impl"],
        attn_impl=p["attn_impl"], mask_full_devices=p["mask_full_devices"],
        scale=ScaleConfig(segment=p["segment"], gnn_chunk=p["gnn_chunk"]))


def ppo_config(p: Dict[str, Any]):
    from repro.core.ppo import PPOConfig
    fields = {f.name for f in dataclasses.fields(PPOConfig)}
    return PPOConfig(**{k: v for k, v in p.items() if k in fields})


def trainer(pcfg, ppo, seed: int, params):
    """A PPO trainer that starts from the benchmark's weights."""
    from repro.core.ppo import PPOTrainer, TrainState
    from repro.optim import AdamConfig, adam_init
    state = TrainState(params=params,
                       opt_state=adam_init(params, AdamConfig(lr=ppo.lr)),
                       baselines={}, baseline_counts={})
    return PPOTrainer(pcfg, ppo, seed=seed, state=state)
