"""Decode slot use: tokens the decode steps delivered, over slots x decode
steps run, in the rounds that ended inside the window.  The rest is
decode work on rows that were prefilling, idle or already finished."""
from readers import rounds_in_window


def read(run):
    rounds = rounds_in_window(run)
    steps = sum(r["steps"] for r in rounds)
    if not steps:
        return None
    decoded = sum(r["emitted"] - r["first_tokens"] for r in rounds)
    return 100.0 * decoded / (run["cell"]["n_slots"] * steps)
