from benchmark.tests.test_sdar_walk_metrics import *  # noqa: F401,F403  (the two metric files' four tests count in tier-1)
