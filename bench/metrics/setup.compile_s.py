"""Seconds of set-up spent compiling programs or loading them from the
persistent cache (`/jax/core/compile/backend_compile_duration` events)."""


def read(run):
    return run.get("setup_compile_s")
