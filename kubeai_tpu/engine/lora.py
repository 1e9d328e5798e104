"""LoRA adapter runtime: PEFT checkpoints -> the stacked adapter bank.

The reference delegates adapter serving entirely to vLLM's
/v1/load_lora_adapter (ref: internal/vllmclient/client.go); here the
engine owns it: PEFT-format checkpoints (adapter_config.json +
adapter_model.safetensors) are parsed into the batched multi-LoRA bank
(the family's `init_lora_bank`) and installed with device scatters —
loading or unloading an adapter never recompiles the serving functions.
"""

from __future__ import annotations

import glob
import json
import os
import threading

import jax.numpy as jnp
import numpy as np

from kubeai_tpu.models import family
from kubeai_tpu.models.base import ModelConfig

# PEFT target_modules name -> our param name.
TARGET_MAP = {
    "q_proj": "wq",
    "k_proj": "wk",
    "v_proj": "wv",
    "o_proj": "wo",
    "gate_proj": "wg",
    "up_proj": "wu",
    "down_proj": "wd",
}


def load_peft_checkpoint(path: str) -> tuple[dict, dict[str, dict[int, tuple[np.ndarray, np.ndarray]]], float]:
    """Returns (config, {target: {layer: (A [r,in], B [out,r])}}, scale)."""
    with open(os.path.join(path, "adapter_config.json")) as f:
        cfg = json.load(f)
    rank = cfg.get("r", 8)
    alpha = cfg.get("lora_alpha", rank)
    scale = alpha / rank

    files = sorted(glob.glob(os.path.join(path, "adapter_model*.safetensors")))
    tensors: dict[str, np.ndarray] = {}
    if files:
        from safetensors import safe_open

        for fpath in files:
            with safe_open(fpath, framework="np") as reader:
                for name in reader.keys():
                    tensors[name] = reader.get_tensor(name)
    else:
        import torch

        bins = sorted(glob.glob(os.path.join(path, "adapter_model*.bin")))
        if not bins:
            raise FileNotFoundError(f"no adapter weights under {path}")
        for fpath in bins:
            for name, t in torch.load(fpath, map_location="cpu", weights_only=True).items():
                tensors[name] = t.float().numpy()

    out: dict[str, dict[int, tuple[np.ndarray, np.ndarray]]] = {}
    for name, arr in tensors.items():
        # e.g. base_model.model.model.layers.3.self_attn.q_proj.lora_A.weight
        parts = name.split(".")
        try:
            layer_idx = int(parts[parts.index("layers") + 1])
        except (ValueError, IndexError):
            continue
        target = next((t for t in TARGET_MAP if t in parts), None)
        if target is None:
            continue
        kind = "A" if "lora_A" in parts else "B" if "lora_B" in parts else None
        if kind is None:
            continue
        slot = out.setdefault(TARGET_MAP[target], {}).setdefault(layer_idx, [None, None])
        slot[0 if kind == "A" else 1] = arr
    return cfg, out, scale


class AdapterRuntime:
    """Owns the adapter bank + name->row assignment for one engine.

    On a multi-host gang (*mesh* spans processes) the bank is kept as a
    HOST numpy mirror and published as replicated global-mesh arrays via
    make_array_from_callback on every change: every rank runs the same
    (stream-ordered) load/unload against the same checkpoint files, so
    the mirrors agree bit-for-bit, and eager device scatters — which
    multi-process arrays forbid — are never needed. Single-host keeps
    the incremental device-scatter path (no full re-upload per load)."""

    def __init__(self, config: ModelConfig, max_adapters: int = 8, max_rank: int = 64, dtype=None, mesh=None):
        import jax

        self.config = config
        self.max_adapters = max_adapters
        self.max_rank = max_rank
        self._mesh = mesh
        self._multiproc = mesh is not None and jax.process_count() > 1
        # Row 0 is the reserved no-adapter identity.
        if self._multiproc:
            shapes = jax.eval_shape(
                lambda: family(config).init_lora_bank(config, max_adapters + 1, max_rank, dtype)
            )
            self._host_bank = {
                k: np.zeros(s.shape, s.dtype) for k, s in shapes.items()
            }
            self.bank = self._publish_global()
        else:
            self.bank = family(config).init_lora_bank(config, max_adapters + 1, max_rank, dtype)
        self._rows: dict[str, int] = {}
        # Per-row generation, bumped whenever a row's weights change
        # (load/reload/unload): rows are recycled, so consumers caching
        # anything derived from a row (e.g. KV prefix reuse) must key on
        # (row, generation), never the bare index.
        self._row_gen: dict[int, int] = {}
        self._lock = threading.Lock()

    def row_for(self, name: str | None) -> int:
        if not name:
            return 0
        with self._lock:
            return self._rows.get(name, 0)

    def row_sig(self, name: str | None) -> tuple[int, int]:
        """(row, generation) identity of the adapter's current weights."""
        with self._lock:
            row = self._rows.get(name, 0) if name else 0
            return row, self._row_gen.get(row, 0)

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._rows)

    def _publish_global(self) -> dict:
        """Host mirror -> replicated global-mesh arrays (multiproc only;
        a full re-upload per admin op, which is rare)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        repl = NamedSharding(self._mesh, PartitionSpec())
        return {
            k: jax.make_array_from_callback(v.shape, repl, lambda idx, v=v: v[idx])
            for k, v in self._host_bank.items()
        }

    def load(self, name: str, path: str) -> None:
        cfg, targets, scale = load_peft_checkpoint(path)
        rank = cfg.get("r", 8)
        if rank > self.max_rank:
            raise ValueError(f"adapter rank {rank} exceeds engine max {self.max_rank}")
        with self._lock:
            if name in self._rows:
                row = self._rows[name]
            else:
                used = set(self._rows.values())
                free = [i for i in range(1, self.max_adapters + 1) if i not in used]
                if not free:
                    raise RuntimeError(f"adapter capacity {self.max_adapters} exhausted")
                row = free[0]

            # Phase 1 — build every row update up front: all shape/name
            # failures happen here, BEFORE any bank state is touched, so
            # a rejected checkpoint can never leave a half-written row
            # (the multiproc host mirror is mutated in place and has no
            # copy-on-write to fall back on).
            L = self.config.num_layers
            dtype = self.bank["wq_A"].dtype
            updates: dict[str, tuple[np.ndarray, np.ndarray]] = {}
            for target, layers in targets.items():
                A_key, B_key = target + "_A", target + "_B"
                din = self.bank[A_key].shape[2]
                dout = self.bank[B_key].shape[3]
                A = np.zeros((L, din, self.max_rank), np.float32)
                Bm = np.zeros((L, self.max_rank, dout), np.float32)
                for li, (a, b) in layers.items():
                    if a is None or b is None or li >= L:
                        continue
                    # PEFT stores A [r, in], B [out, r]; bank wants
                    # [in, r] / [r, out], zero-padded to max_rank.
                    A[li, :, : a.shape[0]] = a.T
                    Bm[li, : b.shape[1], :] = b.T
                updates[target] = (A, Bm)

            # Phase 2 — apply (infallible) and publish with one
            # reference assignment at the end: the engine thread reads
            # self.bank without a lock, and mutating the live dict
            # target-by-target would let a decode chunk dispatched
            # mid-reload run with mixed old/new A/B weights.
            bank = dict(self.bank)
            for target, (A, Bm) in updates.items():
                A_key, B_key = target + "_A", target + "_B"
                if self._multiproc:
                    self._host_bank[A_key][:, row] = A.astype(dtype)
                    self._host_bank[B_key][:, row] = Bm.astype(dtype)
                else:
                    bank[A_key] = bank[A_key].at[:, row].set(jnp.asarray(A, dtype))
                    bank[B_key] = bank[B_key].at[:, row].set(jnp.asarray(Bm, dtype))
            if self._multiproc:
                self._host_bank["scale"][row] = scale
                bank = self._publish_global()
            else:
                bank["scale"] = bank["scale"].at[row].set(scale)
            self.bank = bank  # atomic snapshot publish
            self._rows[name] = row
            self._row_gen[row] = self._row_gen.get(row, 0) + 1

    def unload(self, name: str) -> bool:
        with self._lock:
            row = self._rows.pop(name, None)
            if row is None:
                return False
            if self._multiproc:
                for key in self._host_bank:
                    if key.endswith("_A") or key.endswith("_B"):
                        self._host_bank[key][:, row] = 0
                self._host_bank["scale"][row] = 0.0
                bank = self._publish_global()
            else:
                bank = dict(self.bank)  # atomic snapshot publish (see load)
                for key in list(bank):
                    if key.endswith("_A") or key.endswith("_B"):
                        bank[key] = bank[key].at[:, row].set(0.0)
                bank["scale"] = bank["scale"].at[row].set(0.0)
            self.bank = bank
            self._row_gen[row] = self._row_gen.get(row, 0) + 1
            return True
