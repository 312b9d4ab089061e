"""Mesh construction over TPU slices.

Axes (scaling-book conventions):

- ``dp``   -- data parallel: independent batch lanes (serving-layer worker
  replication maps here when one engine spans multiple hosts).
- ``tp``   -- tensor parallel: attention heads / MLP hidden sharded; the
  all-reduce rides ICI.
- ``pp``   -- pipeline parallel over layer groups (cross-host).
- ``sp``   -- sequence/context parallel (ring attention) for long context.
- ``ep``   -- expert parallel: MoE expert weights and dispatch buffers
  sharded over experts; the token shuffle rides ICI (GSPMD inserts the
  all_to_all from the sharding annotations).

``build_mesh`` lays axes out so that tp is innermost (fastest-varying
device order = closest ICI neighbors), matching how XLA enumerates cores in
a slice.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


@dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.pp * self.sp * self.ep

    def axis_names(self) -> List[str]:
        return ["dp", "pp", "sp", "ep", "tp"]


def build_mesh(
    cfg: MeshConfig, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < cfg.num_devices:
        raise ValueError(
            f"mesh needs {cfg.num_devices} devices, have {len(devices)}"
        )
    devices = devices[: cfg.num_devices]
    arr = np.asarray(devices).reshape(cfg.dp, cfg.pp, cfg.sp, cfg.ep, cfg.tp)
    return Mesh(arr, axis_names=tuple(cfg.axis_names()))


def single_device_mesh() -> Mesh:
    return build_mesh(MeshConfig())


def serving_mesh(
    tp: int = 1, dp: int = 1, devices: Optional[Sequence[jax.Device]] = None
) -> Optional[Mesh]:
    """The engine-startup mesh: dp x tp over the local devices, or None
    when both degrees are 1 (single-chip serving pays zero mesh
    machinery).  Raises when the process cannot see enough devices --
    a silently-shrunk mesh would serve with replicated params and report
    multi-chip throughput it is not getting."""
    tp, dp = max(int(tp), 1), max(int(dp), 1)
    if tp == 1 and dp == 1:
        return None
    return build_mesh(MeshConfig(dp=dp, tp=tp), devices)


def env_parallel_spec() -> dict:
    """``DYN_TP`` / ``DYN_DP`` -> {"tp": n | None, "dp": n | None}: the
    deployment-side override for engine-startup tensor/data parallelism
    (mirrors the DYN_KV_OFFLOAD pattern -- arm the plane without touching
    config).  None means the variable is unset and config decides; a set
    value wins outright, so ``DYN_TP=1`` disarms a config-armed tp.  An
    unparsable value raises: a typo silently falling back to config would
    serve single-chip while the operator believes TP is armed -- the
    worst kind of disarm, since the output is identical either way."""
    import os

    out = {}
    for key, name in (("tp", "DYN_TP"), ("dp", "DYN_DP")):
        raw = os.environ.get(name)
        if raw is None or raw.strip() == "":
            out[key] = None
            continue
        try:
            out[key] = max(int(raw), 1)
        except ValueError:
            raise ValueError(
                f"{name}={raw!r} is not an integer parallel degree"
            ) from None
    return out
