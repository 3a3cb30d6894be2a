import jax

# Full-precision twiddles and f64 oracle paths throughout the suite.
# (The dry-run sets its own XLA_FLAGS in a separate process; tests always
# see the default single host device.)
jax.config.update("jax_enable_x64", True)

# Tests never write JAX's persistent compilation cache: entry points the
# tests call in-process (the CLI) point it into the checkout, and compiles
# for a described TPU topology there could not be read back.
jax.config.update("jax_enable_compilation_cache", False)
