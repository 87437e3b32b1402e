"""The benchmark's two forks, held to their originals in tier-1 (ROADMAP
D14, D11): its trace shapes against `trace/synth.py`, event for event, and
its stock plain reference against the golden model. The tests are the
benchmark's own (`benchmark/tests/test_benchmark.py`), imported here so
that a change to `synth.py` or `golden/sim.py` that leaves a fork behind
fails `pytest tests/`, not only `pytest benchmark/tests`."""

from benchmark_modules import load_benchmark_tests

_theirs = load_benchmark_tests()
test_generator_equals_the_programs = _theirs.test_generator_equals_the_programs
test_reference_equals_golden = _theirs.test_reference_equals_golden
