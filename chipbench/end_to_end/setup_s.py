"""Process start to the first timed instant: imports, weights, tracing and lowering, compilation or cache retrieval, warm-up executions, traffic generation."""
NAME = "setup_s"
UNIT = "s"
SOURCE = "host_clock"


def read(obs):
    return obs['setup_s']
