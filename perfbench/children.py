"""The harness's children: each is its own process, because each may import
jax and a chip belongs to one process at a time. `run.py` itself never
imports jax. Copies of chip_smoke.py's children (PR 21), made general over
the configuration file; what belongs to a model family (which tensors a
checkpoint holds, what decides its logits right) is in families/.

    python3 perfbench/children.py checkpoint <dir> <hf_config.json> <seed> [<full checkpoint>]
    python3 perfbench/children.py logits <dir> <seed> <serving.json>
    python3 perfbench/children.py trace <trace.xplane.pb> <platform> <seconds> [<window event regex>]

The last stdout line of each is its JSON result.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def family_of(hf: dict):
    """The family file of a configuration: perfbench/families/<model_type>.py,
    by the `model_type` its published config.json carries (the key the
    program's own loader reads). No default and no table: a family is added
    by adding its file."""
    import importlib
    import re

    name = str(hf.get("model_type"))
    path = os.path.join(HERE, "families", name + ".py")
    if not re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_-]*", name) or not os.path.exists(path):
        raise SystemExit(
            f"no family file for model_type {name!r}: add {os.path.relpath(path, os.path.dirname(HERE))} with "
            "layer_plan, outside_plan and logits (perfbench/families/__init__.py; PERF.md section 4)"
        )
    return importlib.import_module(f"families.{name}")


def child_checkpoint(path: str, hf_config_path: str, seed: str, full: str = "") -> dict:
    """Seeded random weights as an HF safetensors directory beside the
    published config.json: one shard per layer, drawn on all cores (numpy
    draws outside the interpreter lock), each tensor as the family's plan
    says. No jax.

    With *full* (the checkpoint of the same seed at full depth) this is the
    shallow cut the logits check runs on: a shard whose plan is the full
    model's is the same bytes and is linked to it, one whose plan differs is
    written."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file

    with open(hf_config_path) as f:
        hf = json.load(f)
    family = family_of(hf)
    base = None
    if full:
        with open(os.path.join(full, "config.json")) as f:
            base = json.load(f)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f, indent=1)
    L = hf["num_hidden_layers"]
    bf16 = ml_dtypes.bfloat16

    def draw(rng, *shape, scale):
        # Uniform on [-a, a] with a = scale * sqrt(3): the variance of a
        # normal(0, scale) at a third of the cost of drawing one.
        a = np.float32(scale * 3**0.5)
        x = rng.random(shape, dtype=np.float32)
        x *= 2 * a
        x -= a
        return x.astype(bf16)

    def shard(name: str, key: int, plan_of) -> int:
        plan = plan_of(hf)
        if base is not None and plan == plan_of(base):
            os.symlink(os.path.join(full, name), os.path.join(path, name))
            return 0
        rng = np.random.default_rng([int(seed), key])
        tensors = {
            tensor: np.ones(shape, bf16) if scale is None else draw(rng, *shape, scale=scale)
            for tensor, shape, scale in plan
        }
        save_file(tensors, os.path.join(path, name))
        return sum(t.nbytes for t in tensors.values())

    def layer(i: int) -> int:
        return shard(f"model-layer-{i:03d}.safetensors", i, lambda hf: family.layer_plan(hf, i))

    def outside() -> int:
        return shard("model-outside-layers.safetensors", 10_000, family.outside_plan)

    with ThreadPoolExecutor(max_workers=min(os.cpu_count() or 1, 12)) as pool:
        jobs = [pool.submit(outside)] + [pool.submit(layer, i) for i in range(L)]
        written = [j.result() for j in jobs]
    report = {"bytes": sum(written), "shards": L + 1}
    if full:
        report["linked"] = written.count(0)
    return report


def child_logits(path: str, seed: str, serving_path: str) -> dict:
    """The family's logits check on the checkpoint at *path* (the shallow
    cut), with the cell's `serving` keys."""
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    with open(serving_path) as f:
        serving = json.load(f)
    return family_of(hf).logits(path, seed, serving)


def child_trace(path: str, platform: str, seconds: str, window_event: str = "") -> dict:
    """*window_event* (`--trace 2`): the host event that spans the traced
    interval, as the engine's reply named it, in place of trace.json's."""
    sys.path.insert(0, HERE)
    import trace_reduce

    with open(os.path.join(HERE, "trace.json")) as f:
        spec = json.load(f)
    part = {**spec[platform], "profile_seconds": float(seconds)}
    if window_event:
        part["window_event"] = window_event
    planes, window, notes = trace_reduce.read_xplane(path, part)
    out = trace_reduce.reduce_events(planes, window)
    out["window_from"] = notes["window_from"]
    out["plane_names"] = [p["name"] for p in notes["planes"]]
    out["bytes"] = os.path.getsize(path)
    return out


CHILDREN = {"checkpoint": child_checkpoint, "logits": child_logits, "trace": child_trace}

if __name__ == "__main__":
    print(json.dumps(CHILDREN[sys.argv[1]](*sys.argv[2:])), flush=True)
