"""The one traffic generator. A mix is a data file: loop kind, clients and
a list of queries with whole-number weights. Every seed gets the same
multiset of queries, block by block, in another order."""

import numpy as np


def sequence(traffic, seed):
    """Endless iterator of query names. One block holds each query
    `weight` times; each block is shuffled from the seed."""
    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        raise SystemExit("this harness drives a closed loop of one client; "
                         f"the mix asks for {traffic.get('loop')!r} with "
                         f"{traffic.get('clients')!r} clients")
    block = [q["query"] for q in traffic["queries"]
             for _ in range(int(q["weight"]))]
    rng = np.random.default_rng(int(seed))
    while True:
        for i in rng.permutation(len(block)):
            yield block[i]
