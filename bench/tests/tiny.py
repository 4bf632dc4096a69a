"""A cell small enough for the CPU: the cifar10-resnet18 cell's files
with a two-stage-wide teacher, 8x8 images, three nodes of four batches."""
from __future__ import annotations

from bench import harness


def tiny_cell(traffic: str = "kd", dtype: str = "float32",
              limits_of: str = "c10r18.kd") -> dict:
    cell = harness.load_cell(limits_of)
    cfg = dict(cell["config"])
    cfg.update(teacher={"resnet_blocks": [1, 1, 1, 1], "resnet_width": 8},
               input_hw=[8, 8, 3], proto_dim=16, nodes=3,
               images_per_node=8, batch_size=4, test_images=8,
               dtype=dtype)
    cell.update(name="tiny", config=cfg,
                traffic=harness.load_json(
                    harness.BENCH / "traffic" / f"{traffic}.json"))
    cell["workload"] = dict(cell["workload"], reference_block_nodes=2)
    return cell


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
