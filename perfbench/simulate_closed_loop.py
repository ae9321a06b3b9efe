"""How much of a closed-loop serving cell's spread over seeds is the
TRAFFIC'S: the loop of ``drivers/serve_closed_loop_swa.py`` over the
engine's tick (``ServingEngine._step_inner`` with chunked prefill: one
prefill chunk a tick, of the one request that is mid-prefill, then one
decode step for every active lane) replayed on the host from the
generator's own lengths for a seed and two measured program times. No
device, no model: what moves from seed to seed here is which lengths fall
inside the window and nothing else.

    python3 perfbench/simulate_closed_loop.py --seeds 3000000311 3000000312 \\
        --decode-ms 20.5 --chunk-ms 27.5

One JSON line a seed (``serve_tokens_per_s`` as the cell counts it: tokens
delivered inside the window over its length; the window opens when the first
round has returned), then the spread over the seeds as the driver takes it.
The times are what a traced run of the cell reads: the tick with its chunk
(``batch.tick_ms_p50``) less the chunk program (``batch.prefill_ms_p50``),
and the chunk program with the host's work around it (PERF.md section 7).
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness, traffic as traffic_gen  # noqa: E402


def simulate(cell, seed: int, seconds: float, decode_s: float,
             chunk_s: float) -> dict:
    job, chunk = cell.traffic, cell.deploy["prefill_chunk"]
    vocab = cell.config["model"]["vocab_size"]
    streams = [traffic_gen.client_stream(job, seed, c, vocab)
               for c in range(job["clients"])]
    queue = collections.deque()   # [client, chunks left, tokens left]
    active, prefilling = [], None
    waiting = set(range(len(streams)))   # clients with nothing in flight
    first_round, rounds = set(waiting), collections.Counter()
    now, start, stamps, done = 0.0, None, [], 0
    while start is None or now < start + seconds:
        for c in sorted(waiting):
            request = next(streams[c])
            queue.append([c, -(-len(request.prompt) // chunk),
                          request.max_new_tokens])
            rounds[c] += 1
        waiting.clear()
        if start is None and not first_round:
            start = now
        if prefilling is None and queue:
            prefilling = queue.popleft()
        if prefilling is not None:
            now += chunk_s
            prefilling[1] -= 1
            if not prefilling[1]:         # the last chunk gives a token
                prefilling[2] -= 1
                stamps.append(now)
                active.append(prefilling)
                prefilling = None
        if active:                        # the one just promoted too
            now += decode_s
            for r in active:
                r[2] -= 1
                stamps.append(now)
        elif prefilling is None:
            raise RuntimeError("nothing in flight")
        for r in [r for r in active if not r[2]]:
            active.remove(r)
            waiting.add(r[0])
            if rounds[r[0]] == 1:
                first_round.discard(r[0])
            done += start is not None
    inside = sum(start <= t <= start + seconds for t in stamps)
    return {"seed": seed, "serve_tokens_per_s": inside / seconds,
            "requests_returned_in_window": done, "setup_round_s": start}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload",
                        default="smallthinker-l8-serve-longdoc-gen")
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--decode-ms", type=float, required=True)
    parser.add_argument("--chunk-ms", type=float, required=True)
    args = parser.parse_args()
    cell = harness.load_cell(args.workload)
    rates = []
    for seed in args.seeds:
        out = simulate(cell, seed, args.seconds, args.decode_ms / 1e3,
                       args.chunk_ms / 1e3)
        rates.append(out["serve_tokens_per_s"])
        print(json.dumps(out), flush=True)
    if len(rates) > 1:
        q1, _, q3 = statistics.quantiles(rates, n=4)
        print(json.dumps({"median": statistics.median(rates),
                          "spread": (q3 - q1) / statistics.median(rates)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
