"""`ycsb_like` in the retry regime (`retry_regime.py`: over a fifth of the
requests retried, join-eligible reads demoted and beaten by writers):
`Engine` and the benchmark's stock plain reference, which the cell
`rung3.ycsb-a` is held to, against the golden model to the cycle and in
every counter, on rung 3's machine and on the plain one."""

import numpy as np
import pytest

from benchmark_modules import assert_reference_equals_golden  # puts benchmark/ on the path

import reference
from primesim_tpu.config.machine import MachineConfig
from retry_regime import golden_in_the_regime

CASES = [(64, True), (64, False), (256, True), (256, False)]
IDS = ["64-router", "64-plain", "256-router", "256-plain"]


@pytest.mark.parametrize("n_cores,router", CASES, ids=IDS)
def test_engine_equals_golden_in_the_retry_regime(n_cores, router):
    from primesim_tpu.sim.engine import Engine

    machine, trace, gold = golden_in_the_regime(n_cores, router)
    eng = Engine(MachineConfig.from_dict(machine), trace, chunk_steps=8)
    eng.run()
    assert not eng.has_sync  # loads and stores only: the program of `rung3.rand-ws1m`
    assert eng.steps_run == -(-gold.step_count // 8) * 8
    np.testing.assert_array_equal(eng.cycles, gold.cycles)
    np.testing.assert_array_equal(np.asarray(eng.state.ptr), gold.ptr)
    for k, v in gold.counters.items():
        np.testing.assert_array_equal(eng.counters[k], v, err_msg=k)


@pytest.mark.parametrize("n_cores,router", CASES, ids=IDS)
def test_stock_reference_equals_golden_in_the_retry_regime(n_cores, router):
    machine, trace, gold = golden_in_the_regime(n_cores, router)
    ref = assert_reference_equals_golden(reference, machine, trace.events, gold=gold)
    assert sum(ref.counters["retries"]) == int(gold.counters["retries"].sum())
    assert bool(sum(ref.counters["noc_contention_cycles"])) == router
