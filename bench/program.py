"""The one file through which the benchmark calls the program under test.

It uses only this surface of the program (the contract later changes
keep, or change together with this file in a benchmark change):

* ``repro.configs.get_config(arch)`` and ``dataclasses.replace`` on the
  result, with the fields mapped from the config file below;
* ``Engine(cfg, params, max_len=, paged=True, block_size=, seed=)``,
  plus ``decode_kernel=`` only where a cell file names one;
* ``Scheduler(engine, n_slots=, chunk_size=, chunked_prefill=True)``
  and its ``submit``, ``step``, ``has_work`` and ``stats``;
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

# config-file key -> program ModelConfig field
FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim", "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "compute_dtype", "kv_cache_dtype": "kv_posit",
}


def enable_compile_cache() -> str:
    return compile_cache.enable()


def program_config(c: dict):
    fields = {FIELDS[k]: v for k, v in c.items() if k in FIELDS}
    if "kv_lora_rank" in c:
        fields["head_dim"] = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    else:
        fields["head_dim"] = c.get("head_dim", c["hidden_size"]
                                   // c["num_attention_heads"])
    cfg = dataclasses.replace(configs.get_config(c["program_arch"]), **fields)
    if cfg.mla != ("kv_lora_rank" in c) or cfg.sliding_window \
            or cfg.is_moe or cfg.act != c["hidden_act"] \
            or cfg.scale_embed or cfg.norm_plus_one:
        raise ValueError(f"{c['name']}: the program's {c['program_arch']} "
                         "differs from the config file beyond the mapped "
                         "fields")
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
                               chunk_size=cell["chunk_size"],
                               chunked_prefill=True)

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
