"""What a configuration's builder hands the harness."""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, ContextManager, Optional


@dataclasses.dataclass
class Deployment:
    cfg: Any                        # the program's GAConfig
    fitness: Callable               # the program's fitness, traced inline
    cost_fn: Optional[Callable]     # the program's cost model for dispatch
    ctx: Any                        # the program's ShardingCtx, or None
    ga_seed: int                    # 32-bit GA seed drawn from --seed
    fitness_name: str               # names the fitness gap: "<name>_gap"
    reference: Callable             # (N, G) genomes -> (N, M) reference
                                    # fitness, or (N,) for one objective
    control: Callable[..., ContextManager]  # the program in the precision
                                            # below, while the context holds
    ga: dict                        # GA settings for the reference
    limits: dict                    # number -> limit, from the config file
    match_tol: float                # largest gene gap of a matched genome
    trace_epochs: int               # epochs in a traced window
    shapes: dict                    # counts the per-layer readers use
    unconverged: Callable = lambda: 0   # reference solves that failed

    @property
    def evals_per_epoch(self) -> int:
        c = self.cfg
        return c.generations_per_epoch * c.num_islands * c.pop_per_island


def ga_seed(seed: int) -> int:
    """A 32-bit GA seed from any whole ``--seed`` (one seed, one stream)."""
    return int.from_bytes(hashlib.sha256(str(int(seed)).encode()).digest()[:4],
                          "little")


def ga_settings(conf: dict) -> dict:
    """The GA settings the reference reads, straight from the config file."""
    keys = ("crossover_eta", "crossover_prob", "mutation_eta",
            "mutation_prob", "lower", "upper", "generations_per_epoch")
    return {k: conf[k] for k in keys}


def program_config(conf: dict, chips: int, seed: int):
    """The program's GAConfig for the config file on ``chips`` chips."""
    from repro.configs.base import GAConfig

    if (conf["mutation_indpb"], conf["num_migrants"],
            conf["migration_pattern"]) != ("1/num_genes", 1, "ring"):
        raise ValueError("the reference runs indpb 1/num_genes and ring "
                         "migration of one migrant")
    return GAConfig(
        num_genes=conf["num_genes"], pop_per_island=conf["pop_per_island"],
        num_islands=conf["islands_per_chip"] * chips,
        num_objectives=conf.get("num_objectives", 1),
        generations_per_epoch=conf["generations_per_epoch"],
        mutation_prob=conf["mutation_prob"],
        mutation_eta=conf["mutation_eta"],
        crossover_prob=conf["crossover_prob"],
        crossover_eta=conf["crossover_eta"],
        migration_pattern="ring", num_migrants=1,
        lower=conf["lower"], upper=conf["upper"], fused_operators=True,
        seed=seed)

