"""Pallas TPU kernels for the framework's compute hot spots:

  agg/              staleness-weighted buffered aggregation (paper eq. 4)
  rmsnorm/          RMSNorm over the model dim
  flash_attention/  causal / sliding-window flash attention (GQA)

Each kernel ships kernel.py (pl.pallas_call + BlockSpec, compiled unless
called with interpret=True), ops.py (the public entry: the compiled kernel
on TPU, the oracle elsewhere, differentiable on both), and ref.py
(pure-jnp oracle).
"""
import jax


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"
