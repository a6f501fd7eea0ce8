"""Share of the per-worker gradient evaluations the window's steps made
(the program's ``worker_evals`` counter) that Algorithm 1 needs: W on a
full round, two for each sampled row of a difference round
(``scoped.layer_metrics``)."""
from chipbench import scoped


def read(run):
    return scoped.layer_metrics(
        run["trace"], run.get("counters"), run["cell"]["workers"]).get(
            "useful_eval_share.train")
