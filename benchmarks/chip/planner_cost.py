"""Work of one MPiNets planning call, counted from the published shapes
alone, so that it stays the same whatever implements the network.

``model`` is the configuration's ``"model"`` object (the program's
``MpiNetsConfig`` as JSON).  A multiply-accumulate (MAC) is two FLOPs;
biases, activations, norms and max-pools are not counted.  Per problem
and step:

* a set abstraction runs its shared MLP on ``centres x neighbours``
  groups, its first layer ``3 + C_in`` wide;
* GroupAll runs its MLP on every SA2 centre, ``3 + C`` wide;
* the head, the joint encoder and the decoder run once.

Compulsory bytes of the two point kernels, per problem and step and set
abstraction (float32 coordinates, int32 indices):

* FPS (``fps_sample``): the input cloud read once (12 B a point) and the
  ``centres`` indices written (4 B each);
* ball query (``ball_query``): the cloud and the centres read once (12 B
  each) and ``centres x neighbours`` indices written (4 B each).
"""
from __future__ import annotations

from typing import Dict, Sequence

XYZ_BYTES = 12
INDEX_BYTES = 4
JOINTS = 7


def _mlp_macs(fan_in: int, widths: Sequence[int]) -> int:
    macs = 0
    for w in widths:
        macs += fan_in * w
        fan_in = w
    return macs


def _set_abstractions(model: Dict):
    """(input points, centres, neighbours, channels in) of SA1 and SA2."""
    enc = model["encoder"]
    sa1, sa2 = enc["sa1"], enc["sa2"]
    points = (model["robot_points"] + model["obstacle_points"]
              + model["target_points"])
    return ((points, sa1["centres"], sa1["neighbours"],
             3 + enc["in_features"], sa1["widths"]),
            (sa1["centres"], sa2["centres"], sa2["neighbours"],
             3 + sa1["widths"][-1], sa2["widths"]))


def macs_by_part(model: Dict) -> Dict[str, int]:
    """MACs of one problem's step, by part of the network."""
    enc = model["encoder"]
    (_, m1, k1, c1, w1), (_, m2, k2, c2, w2) = _set_abstractions(model)
    return {
        "sa1": m1 * k1 * _mlp_macs(c1, w1),
        "sa2": m2 * k2 * _mlp_macs(c2, w2),
        "sa_global": m2 * _mlp_macs(3 + w2[-1], enc["global_widths"]),
        "fc": _mlp_macs(enc["global_widths"][-1], enc["head_widths"]),
        "policy": (_mlp_macs(JOINTS, model["joint_widths"])
                   + _mlp_macs(enc["head_widths"][-1]
                               + model["joint_widths"][-1],
                               model["decoder_widths"])),
    }


def macs_per_step(model: Dict) -> int:
    return sum(macs_by_part(model).values())


def flops_per_call(model: Dict, problems: int) -> int:
    return 2 * macs_per_step(model) * problems * model["steps"]


def fps_bytes_per_call(model: Dict, problems: int) -> int:
    per = sum(n * XYZ_BYTES + m * INDEX_BYTES
              for n, m, *_ in _set_abstractions(model))
    return per * problems * model["steps"]


def ballquery_bytes_per_call(model: Dict, problems: int) -> int:
    per = sum((n + m) * XYZ_BYTES + m * k * INDEX_BYTES
              for n, m, k, *_ in _set_abstractions(model))
    return per * problems * model["steps"]

