"""The tiny test configuration as a cell."""
import os

from harness.cell import Cell, load_json

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def tiny_cell(watermark="gumbel", traffic=None):
    """A cell of the tiny test configuration (the yi-6b layout at toy
    widths) under a short closed-loop mix."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = load_json(os.path.join(HERE, "tiny.json"))
    cfg["spec"]["watermark"] = watermark
    mix = traffic or load_json(os.path.join(HERE, "tiny_traffic.json"))
    return Cell(name="tiny." + watermark, config_name="tiny",
                traffic_name="tiny", chips=1, config=cfg, traffic=mix,
                end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])
