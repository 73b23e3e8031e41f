"""ray_tpu.ops: Pallas TPU kernels for the hot ops.

Each op ships a pure-jnp reference implementation (used on CPU test meshes and
as the numerical oracle) and a Pallas TPU kernel used on real hardware.

Every function that holds a ``pl.pallas_call`` is a module-level
``jax.jit(..., inline=True)``. ``pallas_call`` traces its kernel's body anew at
every call (it wraps the call in a jit of its own, a new function each time, so
JAX's trace cache, keyed by the function, never serves a repeat), and a
replica's start made the same call once a layer and a program: hundreds of
traces of a few bodies (PERF.md section 6, PR 58). A module-level jit has one
identity, so the cache serves every call of equal shapes, dtypes and statics,
inside a program and across the programs of a process; ``inline=True`` writes
the cached equations into the caller's jaxpr, which is then what it was
without the jit: no nested call in any program. So what differs between equal
layers is an OPERAND (the layer's index, a count of live slots or tiles,
lengths, tables) and a static is a hashable VALUE (a scale, a window, a block
size, a dtype), never a function made at the call.
"""
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ulysses import ulysses_attention

__all__ = ["flash_attention", "mha_reference", "ring_attention", "ulysses_attention"]
