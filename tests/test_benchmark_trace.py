from benchmark.tests.test_benchmark_trace import *  # noqa: F401,F403  (the 18 tests of the traced run count in tier-1)
