"""gcn-paper — the survey's own workload: a multi-layer GCN on a large graph.

This id routes the launcher to the distributed-GNN engine (src/repro/core)
rather than the transformer stack. The config below is the full-graph
production workload used by the GNN dry-run and the SpMM benchmarks
(ogbn-papers100M-like scale, synthetic power-law graph).
"""
import dataclasses

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class GNNWorkloadConfig:
    name: str = "gcn-paper"
    num_vertices: int = 1_048_576  # 2**20: divisible by 256- and 512-chip meshes
    avg_degree: int = 16
    feature_dim: int = 256
    hidden_dim: int = 256
    num_classes: int = 64
    num_layers: int = 3
    model: str = "gcn"  # gcn | sage | gat | gin
    execution_model: str = "spmm_1d"  # see core.execution.spmm_models
    protocol: str = "broadcast"  # broadcast | p2p | pipeline | async
    partition: str = "ldg"  # hash | range | ldg | block | metis_like
    lr: float = 0.05  # full-graph SGD step; the engine's default 0.5 diverges
    #   at these widths (loss rises from the first step)


CONFIG = GNNWorkloadConfig()

# The generator `build_graph` uses.  The docstring's power-law graph cannot be
# generated at 2^20 vertices yet (`powerlaw_graph` is a per-vertex Python
# loop, and power-law ELL tables are ROADMAP item R1).
GRAPH_GENERATOR = "er_graph"


def build_graph(cfg: GNNWorkloadConfig = CONFIG, seed: int = 0):
    """The config's graph: vertices, average degree, feature width and
    classes as stated, edges from the vectorised Erdős–Rényi generator."""
    from repro.core.graph import er_graph

    return er_graph(cfg.num_vertices, avg_degree=cfg.avg_degree,
                    feature_dim=cfg.feature_dim,
                    num_classes=cfg.num_classes, seed=seed)


def engine_config(cfg: GNNWorkloadConfig = CONFIG, **overrides):
    """A DistGNNEngine config at the workload's widths, depth, model,
    partitioner and step size; ``overrides`` set the other engine fields."""
    from repro.core.engine import EngineConfig

    fields = dict(model=cfg.model, hidden=cfg.hidden_dim,
                  num_layers=cfg.num_layers, partitioner=cfg.partition,
                  lr=cfg.lr)
    fields.update(overrides)
    return EngineConfig(**fields)


def smoke_config() -> GNNWorkloadConfig:
    return GNNWorkloadConfig(
        name="gcn-paper-smoke",
        num_vertices=256,
        avg_degree=8,
        feature_dim=32,
        hidden_dim=32,
        num_classes=8,
        num_layers=2,
    )


# keep a ModelConfig-shaped alias so generic tooling that only prints names
# does not special-case; the launcher dispatches on isinstance.
MODEL_CONFIG_PLACEHOLDER = ModelConfig(name="gcn-paper", family="dense", source="arXiv:2211.00216")
