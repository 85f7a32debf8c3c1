"""Arrival kind ``onoff``: open loop in bursts, ``rate_on_per_s`` for
``on_s`` seconds, then ``rate_off_per_s`` for ``off_s`` seconds, repeated
from the start of the warm-up; each burst and lull is one span."""
from bench import traffic


def make(mix, rng, seconds, vocab):
    arr = mix["arrival"]
    spans, t = [], -float(mix.get("warmup_s", 0.0))
    while t < seconds:
        for rate, length in ((arr["rate_on_per_s"], arr["on_s"]),
                             (arr["rate_off_per_s"], arr["off_s"])):
            length = min(length, seconds - t)
            if length > 0:
                spans.append((t, length, rate))
            t += length
    return traffic.open_loop(mix, spans, rng, vocab)
