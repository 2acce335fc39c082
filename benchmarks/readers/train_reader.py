"""The training cell's numbers, from the fenced step boundaries inside
the window. `what`: "tok_s_chip", "step_ms" or "mfu"."""
from benchmarks.harness import costs


def read(run, what, **_):
    if run.get("kind") != "train" or not run.get("steps"):
        return None
    span = run["steps"][-1] - run["window_open"]
    n = len(run["steps"])
    if what == "step_ms":
        return 1000.0 * span / n
    rate = n * run["tokens_per_step"] / span / run["chips"]
    if what == "tok_s_chip":
        return rate
    if not run.get("peaks"):
        return None
    flops = costs.train_flops_per_token(run["model"], run["seq_len"])
    return 100.0 * rate * flops / run["peaks"]["bf16_flops"]
