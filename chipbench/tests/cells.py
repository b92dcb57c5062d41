"""Small cells for the CPU tests: the harness's own generators over
configurations and traffic at sizes a test run holds, written as files
under a root of their own, as a later change would add them."""
import json
import os

import harness

CAMPAIGN = {
    "kind": "campaign",
    "spaces": {"devices": ["tpu_v5e"],
               "problems": {"hotspot": {"h": 256, "w": 512}}},
    "repeats": 2, "cutoff": 0.95, "engine": "jax"}
GA = {"strategy": "genetic_algorithm",
      "hyperparams": [{"method": "uniform", "popsize": 10, "maxiter": 5,
                       "mutation_chance": 10},
                      {"method": "two_point", "popsize": 10, "maxiter": 5,
                       "mutation_chance": 10}]}
SA = {"strategy": "simulated_annealing",
      "hyperparams": [{"T": 1.0, "T_min": 0.01, "alpha": 0.9925,
                       "maxiter": 1}]}
RECORD = {
    "kind": "live_record", "kernel": "flash_attention",
    "problem": {"bh": 4, "bh_kv": 2, "seq": 256, "d": 64},
    "itemsize": 4, "repeats": 1,
    "space": {"tunables": {"block_q": [64, 128, 256],
                           "block_kv": [128, 256, 512],
                           "acc_dtype": ["f32", "bf16"]},
              "divides": {"seq": ["block_q", "block_kv"]}}}
SWEEP = {"check": {"sample": 2, "max_abs_error": 0.05}}

BENCH = {
    "workloads": [
        {"name": "tiny-ga", "config": "tiny-hotspot", "traffic": "tiny-ga",
         "chips": 1},
        {"name": "tiny-sa", "config": "tiny-hotspot", "traffic": "tiny-sa",
         "chips": 1},
        {"name": "tiny-record", "config": "tiny-attn",
         "traffic": "tiny-sweep", "chips": 1}],
    "end_to_end": [
        {"name": "hp_configs_per_s", "unit": "configs/s",
         "workloads": ["tiny-ga", "tiny-sa"]},
        {"name": "configs_recorded_per_s", "unit": "configs/s",
         "workloads": ["tiny-record"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "compile_share.hypertune", "unit": "%",
         "workloads": ["tiny-ga", "tiny-sa"]},
        {"name": "compile_share.record", "unit": "%",
         "workloads": ["tiny-record"]}]}


def write_root(root) -> str:
    """Lay the tiny cells out as files under ``root``; returns it."""
    files = {"configs/tiny-hotspot.json": CAMPAIGN,
             "configs/tiny-attn.json": RECORD,
             "traffic/tiny-ga.json": GA, "traffic/tiny-sa.json": SA,
             "traffic/tiny-sweep.json": SWEEP}
    for rel, body in files.items():
        path = os.path.join(str(root), rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(body, f)
    return str(root)


def cell(root, name: str) -> "harness.Cell":
    return harness.Cell(BENCH, name, root=root)
