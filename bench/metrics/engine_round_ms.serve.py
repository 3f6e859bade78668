"""Host time of the benchmark's `bench.pump` spans that ran an engine
round, per round: what one `ForecastEngine.pump()` costs end to end."""


def read(run):
    rounds = run.counters.get("rounds")
    if not rounds:
        return None
    return 1e3 * run.counters["round_pump_s"] / rounds
