"""Test-session bootstrap: lock the single-device CPU backend before any
test imports repro.launch.dryrun (whose module-level XLA_FLAGS would
otherwise inflate the device count for the whole pytest process — the
512-device setting is for the dry-run subprocesses only); and the scheme
mix that more than one test file shares.
"""
import jax
import pytest

jax.devices()


@pytest.fixture
def sweep11_specs():
    """The chip benchmark's ``sweep11`` scheme mix at n = 16: cs ss pc
    pcmm lb at loads 4 and 16, and ra16.  Seven of its eleven columns
    (five TO schemes and two pc) read one order statistic of a 16-wide
    axis; lb and pcmm read one of a 256-wide window."""
    from repro.core import (cyclic_to_matrix, lb_spec, pc_spec, pcmm_spec,
                            random_assignment_to_matrix, staircase_to_matrix,
                            to_spec)
    n = 16
    specs = []
    for r in (4, 16):
        specs += [to_spec(f"cs{r}", cyclic_to_matrix(n, r)),
                  to_spec(f"ss{r}", staircase_to_matrix(n, r)),
                  pc_spec(r, name=f"pc{r}"),
                  pcmm_spec(r, name=f"pcmm{r}"),
                  lb_spec(r, name=f"lb{r}")]
    specs.append(to_spec("ra16", random_assignment_to_matrix(n, seed=0)))
    return specs
