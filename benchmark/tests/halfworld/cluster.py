"""Test-only second world, its cluster: `c2m-10k`'s cluster with one more
attribute, `zone`, on every other node.  Found by the name
`config.json` gives it; no `BENCHMARK.json` entry names it."""
import numpy as np

from benchmark import cluster as c2m


class Cluster(c2m.Cluster):

    def __init__(self, cfg: dict, seed: int, n_nodes: int | None = None):
        super().__init__(cfg, seed, n_nodes)
        self.zoned = np.arange(self.n) % 2 == 0

    def make_nodes(self) -> list:
        from nomad_tpu.structs.node import compute_node_class
        nodes = super().make_nodes()
        for node, zoned in zip(nodes, self.zoned):
            if zoned:
                node.attributes["zone"] = self.cfg["zone"]
                node.computed_class = compute_node_class(node)
        return nodes
