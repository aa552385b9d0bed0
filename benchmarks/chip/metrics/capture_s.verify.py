"""Seconds per verdict outside relation inference: each report's wall
time minus its summed inference time (capture, decomposition, stitching),
averaged over the window's verdicts."""


def read(run):
    v = [r["outcome"] for r in run.records.get("verdicts", ())
         if "wall_s" in r["outcome"]]
    return sum(o["wall_s"] - o["infer_s"] for o in v) / len(v) if v else None
