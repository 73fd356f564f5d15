"""The one file through which the benchmark calls the program under test.

It uses only this surface of the program (the contract later changes
keep, or change together with this file in a benchmark change):

* ``repro.configs.get_config(arch)`` and ``dataclasses.replace`` on the
  result, with the fields mapped from the config file: the common ones
  below (``FIELDS``) and its lane's (``lanes/<lane>.py`` ``fields``),
  then the lane's guard (``check``), which refuses a program model that
  departs from what the lane's reference implements;
* ``Engine(cfg, params, max_len=, paged=True, block_size=, seed=)``,
  plus ``decode_kernel=`` only where a cell file names one;
* ``Scheduler(engine, n_slots=, chunk_size=)`` (its one mode, the
  chunked paged round) and its ``submit``, ``step``, ``has_work`` and
  ``stats``;
* ``repro.launch.compile_cache.enable()``;
* the parameter tree layout that ``weights.py`` makes.

One stopgap reads the scheduler's slot state (``_slots``) after each
round, to see when each request was admitted and how many tokens it has
delivered: the program has no public per-round token hook yet.  Nothing
else here reaches inside the program.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro import configs  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.runtime.engine import Engine  # noqa: E402
from repro.runtime.scheduler import Scheduler  # noqa: E402

# config-file key -> program ModelConfig field, for every lane
FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "compute_dtype", "kv_cache_dtype": "kv_posit",
}


def enable_compile_cache() -> str:
    return compile_cache.enable()


def program_config(c: dict):
    # imported here, not above: the program's own tests load this file
    # by path for ``Server.progress``, without ``bench/`` on the path
    import lane
    ln = lane.load(c)
    fields = {FIELDS[k]: v for k, v in c.items() if k in FIELDS}
    fields.update(ln.fields(c))
    cfg = dataclasses.replace(configs.get_config(c["program_arch"]), **fields)
    ln.check(cfg, c)
    return cfg


class Server:
    """An engine and its scheduler, as one cell serves with them."""

    def __init__(self, c: dict, cell: dict, params, seed: int):
        self.cfg = program_config(c)
        kw = dict(max_len=cell["max_len"], paged=True,
                  block_size=cell["block_size"], seed=seed % 2**31)
        if "decode_kernel" in cell:
            kw["decode_kernel"] = cell["decode_kernel"]
        self.engine = Engine(self.cfg, params, **kw)
        self.sched = Scheduler(self.engine, n_slots=cell["n_slots"],
                               chunk_size=cell["chunk_size"])

    def submit(self, prompt, max_new_tokens: int) -> int:
        return self.sched.submit(list(prompt), max_new_tokens)

    @property
    def has_work(self) -> bool:
        return self.sched.has_work

    @property
    def stats(self) -> dict:
        return self.sched.stats

    def step(self) -> list:
        """One scheduling round; ``[(rid, tokens)]`` of requests finished."""
        return [(c.rid, c.tokens) for c in self.sched.step()]

    def progress(self) -> dict:
        """``rid -> (tokens delivered, cache positions written)`` for every
        request holding a slot (the stopgap described above)."""
        out = {}
        for s in self.sched._slots:
            if s is None:
                continue
            n = len(s.emitted)
            pos = s.cursor if s.cursor is not None \
                else len(s.req.prompt) + n - 1
            out[s.req.rid] = (n, pos)
        return out

    def close(self):
        """Drop the program's state (arena, jitted programs)."""
        self.sched = None
        self.engine = None
