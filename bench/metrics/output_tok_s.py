"""Output tokens delivered to the host inside the window, divided by the
window (host clock)."""
from readers import tokens_in_window


def read(run):
    return tokens_in_window(run) / run["seconds"]
