"""Build and compile: seconds JAX reported compiling
(`/jax/core/compile/backend_compile_duration`) during set-up."""


def read(run):
    return run.setup["compile_s"]
