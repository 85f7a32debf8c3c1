"""Arrival kind ``poisson``: open loop at ``rate_per_s``.  Requests are
due on their schedule whether or not earlier ones have finished; the
warm-up and the window are one span each."""
from bench import traffic


def make(mix, rng, seconds, vocab):
    warm = float(mix.get("warmup_s", 0.0))
    rate = mix["arrival"]["rate_per_s"]
    return traffic.open_loop(mix, [(-warm, warm, rate), (0.0, seconds, rate)],
                             rng, vocab)
