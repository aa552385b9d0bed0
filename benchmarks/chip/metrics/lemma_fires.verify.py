"""Lemma fires per verdict: the sum of each report's ``lemma_fires``,
averaged over the window's verdicts.  An exact count."""


def read(run):
    v = [r["outcome"] for r in run.records.get("verdicts", ())
         if "fires" in r["outcome"]]
    return sum(o["fires"] for o in v) / len(v) if v else None
