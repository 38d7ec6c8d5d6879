"""Device dispatch: share of the window's device digest dispatches that sent
their pieces as views of the read-back buffer, with no host stage, by the
counter ``digest_dispatches_total{path}``. Nothing to read without a
dispatch."""
COUNTER = "digest_dispatches_total"


def read(obs):
    view = obs.counters.get((COUNTER, "view"), 0.0)
    staged = obs.counters.get((COUNTER, "staged"), 0.0)
    if view + staged <= 0:
        return None
    return 100.0 * view / (view + staged)
