"""Arrival kind ``closed``: ``clients`` callers, each sending the pool's
next request when its last one ends, so the engine always has a backlog.

The pool holds at least ``pool`` requests in blocks of ``block`` (by
default ``clients``), each block a shuffle of the same stratified set of
lengths and sampling settings, so any stretch of whole blocks holds the
same work.  A window takes many blocks when they are small, and then
runs on different seeds differ in their order alone."""
from bench import traffic


def make(mix, rng, seconds, vocab):
    arr = mix["arrival"]
    clients = int(arr["clients"])
    size = int(arr.get("block", clients))
    items = []
    for _ in range(-(-int(arr["pool"]) // size)):
        items.extend(traffic.stratified(mix, size, rng, vocab))
    return traffic.ClosedLoop(float(mix.get("warmup_s", 0.0)),
                              traffic.renumber(items), clients)
