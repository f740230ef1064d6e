"""One import point for the JAX mesh API this repo uses (JAX ≥ 0.9).

* :func:`shard_map` — ``jax.shard_map``; ``mesh`` may be omitted inside a
  :func:`set_mesh` context.
* :func:`set_mesh` — ``jax.set_mesh``.
* :func:`axis_size` — ``jax.lax.axis_size``: static size of a named mesh
  axis from inside ``shard_map``.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map
set_mesh = jax.set_mesh
axis_size = jax.lax.axis_size
