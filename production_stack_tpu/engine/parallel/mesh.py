"""Device mesh construction.

Axes:
  dp — data parallel: replicates the model, shards the decode batch.
  tp — tensor parallel: shards attention heads / MLP channels; XLA emits
       psum over ICI after o_proj and down_proj.
  sp — sequence parallel: ring-attention axis for long-context prefill.

On GKE the axes map onto the physical slice topology (e.g. v5e ``2x4``);
``jax.experimental.mesh_utils`` picks an ICI-friendly device order.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from production_stack_tpu.engine.config import ParallelConfig

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    DP: str = "dp"
    TP: str = "tp"
    SP: str = "sp"


AXES = MeshAxes()


def build_mesh(parallel: ParallelConfig, devices: Optional[list] = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    shape = parallel.mesh_shape  # (dp, tp, sp)
    needed = int(np.prod(shape))
    if needed > len(devices):
        raise ValueError(
            f"Mesh {shape} needs {needed} devices; only {len(devices)} available"
        )
    devices = devices[:needed]
    if devices[0].platform == "cpu":
        # CPU virtual devices have no topology to order by.  On an
        # accelerator a topology create_device_mesh refuses is an error.
        device_array = np.asarray(devices).reshape(shape)
    else:
        device_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(device_array, (AXES.DP, AXES.TP, AXES.SP))


def single_device_mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1), (AXES.DP, AXES.TP, AXES.SP))
