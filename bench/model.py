"""The system under test, set up from a configuration file: the file
itself, its family's code, and the serving engine
``repro.launch.serve.build_engine`` would build with the same flags.

What depends on the model lives in its family, ``families/<family>/``
under the harness's files, named by the file's ``"family"`` key:
``reference.py`` (the plain reference, which imports nothing of the
program, and the model's work counts) and ``program.py`` (the program's
``ArchConfig`` and served weights).
"""
from __future__ import annotations

import dataclasses
import json
import os
from types import ModuleType

import byname

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Family:
    """A family's two files, loaded.

    ``reference``: ``arch(model) -> dict``, ``base_key(seed)``,
    ``logit_gaps(seed, model, seqs)`` and the work counts, each a function
    of ``arch``: ``ops_per_token``, ``span_attention_ops(arch, p0, c)``,
    ``decode_attn_work(arch, contexts) -> (ops, bytes)`` and ``head_ops``.
    ``program``: ``arch_config(conf, precision)`` and
    ``program_params(conf, cfg, seed)``."""
    name: str
    reference: ModuleType
    program: ModuleType


def family(name: str, root: str = HERE) -> Family:
    d = os.path.join(root, "families", name)
    return Family(name, byname.module(os.path.join(d, "reference.py")),
                  byname.module(os.path.join(d, "program.py")))


def build_engine(conf: dict, cfg, params, seed: int):
    """The engine ``launch/serve.build_engine`` builds for the file's
    serving flags (the same ServeConfig), around ``cfg`` and ``params``."""
    from repro.dist.sharding import AxisEnv, set_axis_env
    from repro.serve import ServeConfig, ServingEngine
    s = conf["serving"]
    set_axis_env(AxisEnv())
    scfg = ServeConfig(
        batch_lanes=s["lanes"], max_seq=s["max_seq"], int8_kv=s["int8_kv"],
        temperature=0.0, token_budget=s["token_budget"],
        prefill_chunk=s.get("prefill_chunk", 32), seed=int(seed) % (2 ** 31),
        paged=s["paged"], page_size=s["page_size"],
        pool_pages=s.get("pool_pages", 0), spec_k=s.get("spec_k", 0),
        eos_token=-1)
    return ServingEngine(params, cfg, scfg)
