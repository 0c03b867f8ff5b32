"""repro — JAX reproduction of distributed xPU stencil computations.

Supports JAX 0.9.0 (``jax.shard_map``, ``jax.lax.axis_size``, and
``Compiled.cost_analysis()`` returning a dict are used as-is).
"""
