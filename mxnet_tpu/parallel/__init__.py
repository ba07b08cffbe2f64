"""Parallelism over device meshes.

This layer replaces the reference's entire distribution stack
(src/kvstore/comm.h device reduce, comm_tree.h topology trees,
kvstore_nccl.h RCCL, kvstore_dist.h ps-lite — SURVEY.md §2.3) with
XLA-native SPMD: pick a `jax.sharding.Mesh`, annotate shardings, let GSPMD
insert collectives over ICI/DCN.
"""
from .mesh import make_mesh, data_parallel_sharding, replicated
from .spmd import SPMDTrainStep, megatron_tp_rule
from .pipeline import make_pipeline, stack_stage_params
from .moe import expert_layer, route
from .ring_attention import (blockwise_attention, ring_attention,
                             make_ring_attention, attention_reference)
from ..ops.pallas_flash import flash_attention
from .layout import LayoutManifest
from . import ddp
from . import dist
from . import fault
from . import layout
