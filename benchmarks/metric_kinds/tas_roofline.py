"""Reader ``tas_roofline``: the topology stage's kernel against the
chip's HBM bandwidth.  The least time is the bytes any program that
answers the traced launches' placement questions has to move, over the
bandwidth (benchmarks/peaks.py), and the share is that over the
kernel's device time (``programs``, in the reduced trace).  The bytes
are counted from the cluster (the kind's ``problem()``: the leaves and
the node plane's resources) and the program's own counters of launches
and of the distinct questions they answered while the profiler ran
(``launch_counter``, ``query_counter``), never from the program's
shapes:

- the leaves' free capacity, once a launch: ``tas_leaves`` x
  ``tas_resources`` words;
- a distinct question's request (a word a resource, its pods, its kind
  and its level) and its answer, at least one (leaf, pods) pair.  A
  question asked again in one launch is answered once by any program
  that sees the launch whole, so it is counted once.

Domain states above the leaves, a question's further placements and
the empty tree's plane are not counted: a program may keep them on the
chip or need none.  So the share is a lower bound.  A cell whose kind
counts no leaves, or a run with no launch traced, is nothing to read.
"""

import peaks


def least_bytes(problem: dict, launches: int, queries: int) -> int:
    r = problem["tas_resources"]
    plane = problem["tas_leaves"] * r * peaks.WORD
    question = (r + 3) * peaks.WORD + 2 * peaks.WORD
    return launches * plane + queries * question


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    problem = ctx.get("problem") or {}
    if not trace or "tas_leaves" not in problem:
        return None
    hits = [s for name, s in trace["program_s"].items()
            if name in spec["programs"]]
    traced = ctx["counters"]["traced"]
    launches = traced.get(spec["launch_counter"], 0)
    if not hits or not sum(hits) or not launches:
        return None
    least_s = (least_bytes(problem, launches,
                           traced.get(spec["query_counter"], 0))
               / peaks.peak(ctx["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least_s / sum(hits)
