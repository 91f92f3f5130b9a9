# Pallas TPU kernels for the framework's compute hot spots.
# Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# public wrapper, interpret=True on the CPU only), ref.py (pure-jnp oracle).
