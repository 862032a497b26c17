import os

# the tests run on the CPU: this process and every node process it spawns
# (children inherit the environment); cap compilation parallelism for
# container stability
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false")

import jax  # noqa: E402

jax.config.update("jax_platform_name", "cpu")
