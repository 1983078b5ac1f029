"""Thin wrappers over the JAX mesh APIs the sharded paths use.

Everything multi-device in this codebase (the four-step reorder network,
MoE, pipeline parallel, the sharded EvalPlan) goes through these
helpers, written for the installed JAX (0.9).
"""
from __future__ import annotations

import math

import jax
import numpy as np


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)


def pcast_varying(x, axis: str):
    """Mark a replicated value as device-varying over ``axis``."""
    return jax.lax.pcast(x, (axis,), to="varying")


def use_mesh(mesh):
    """``with use_mesh(mesh):`` — the ambient mesh context."""
    return jax.set_mesh(mesh)


def make_mesh(shape, axis_names, devices=None):
    """``jax.make_mesh`` over all devices, or — when an explicit
    ``devices`` subset is requested (e.g. a scaling sweep meshing over
    the first d of the host's devices) — ``jax.sharding.Mesh`` over
    that subset."""
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axis_names))
    devs = list(devices)
    need = math.prod(shape)
    if len(devs) < need:
        raise ValueError(
            f"make_mesh: mesh shape {tuple(shape)} needs {need} devices, "
            f"have {len(devs)}")
    return jax.sharding.Mesh(
        np.array(devs[:need]).reshape(tuple(shape)), tuple(axis_names))
