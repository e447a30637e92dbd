"""Test-session bootstrap: lock the single-device CPU backend before any
test imports repro.launch.dryrun (whose module-level XLA_FLAGS would
otherwise inflate the device count for the whole pytest process — the
512-device setting is for the dry-run subprocesses only).
"""
import jax

jax.devices()
