"""Seconds of XLA compilation during set-up, persistent-cache loads
included (jax's ``backend_compile_duration`` events)."""


def read(m):
    return m["setup_compile"]["xla_compile_s"]
