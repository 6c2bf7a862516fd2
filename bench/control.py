"""The control of a cell's comparison: the reference of the
configuration's ranking computed one precision down (bfloat16 for the
configuration's float32) put in the program's place, at the cell's own
size.  It has to come out not correct; its readings set the upper end
of ``score_gap``'s limit.

    python3 -m bench.control --workload <cell> --seeds 1,2,3

For each seed: the cell's corpus and a run's worth of sampled queries
(the mix's ``sample``), answered by the bfloat16 reference and compared
with the float64 one exactly as a run compares the server's answers.
One JSON line per seed.  It runs on the host alone.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import corpus, reference, run, traffic


def read(config: dict, mix: dict, seed: int) -> dict:
    c = corpus.generate(config, seed)
    n = int(mix["sample"])
    qs = traffic.queries(mix, c.df(), c.token_counts(), c.num_docs, n, seed)
    terms = {int(t) for q in qs for t in q}
    k = int(config["k"])
    ranking = run.load_ranking(config["ranking"])
    exact = ranking.Reference(c, terms)
    low = ranking.Reference(c, terms, precision="bfloat16")
    got = reference.compare([(q, *low.answer(q, k).served(k)) for q in qs],
                            exact, k)
    checks, correct = reference.verdict(got, 0, config["limits"])
    return {**got, "checks": checks, "correct": correct}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = run.load_benchmark()
    _, _, config, mix = run.resolve_cell(bench, args.workload)
    for s in args.seeds.split(","):
        print(json.dumps({"workload": args.workload, "seed": int(s),
                          **read(config, mix, int(s))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
