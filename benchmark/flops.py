"""Operations and bytes of a configuration's training step, counted from
its layer table, and the chip's peaks.

The table is the family's own: ``layer_table(config, traffic)`` is
``benchmark/families/<config.network.family>.py::layers(config, traffic)``
and nothing more.  **A family file exports**

* ``layers(config, traffic) -> [row]``: every layer of one training step on
  one sample of the traffic (an image, a sequence), from the loaded
  configuration and traffic files alone, at the extent the traffic sends
  and not of any padding it is laid into: computing the padding is work the
  algorithm does not require;
* ``STAGES``: the named scopes a device op of the step can lie under
  (``step.unscoped_ms`` is what lies under none).

A table row is one layer: ``name``, the named ``scope`` its device time is
found under, ``times`` (how often it runs a sample: 1, the ROIs of an image,
the tokens of a sequence; written by the family from the configuration),
``grad`` ('none': frozen and nothing trainable before it, forward only;
'weight', 'input' or 'both') and what one forward application costs.  That
is either a ``kind`` this file counts from the row's shapes ('conv',
'dense', 'roialign') or the row's own ``flops`` and ``bytes``: numbers the
family computed (a batched matrix product, a scan, a gather) with functions
that stay in the family's file.  Only operations the algorithm requires are
counted: a multiply-add is two, recomputation counts nothing, and a backward
pass costs one forward's worth for each of the input gradient and the weight
gradient that exists.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
PASSES = {"none": 1, "weight": 2, "input": 2, "both": 3}


def peaks(device_kind: str) -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def family(net: Dict):
    """The module ``benchmark/families/<net.family>.py``."""
    return importlib.import_module(f"benchmark.families.{net['family']}")


def layer_table(config: Dict, traffic: Dict) -> List[Dict]:
    """Every layer of ``config``'s training step on one sample of
    ``traffic``, as its family states them."""
    return family(config["network"]).layers(config, traffic)


def conv(name, scope, cin, cout, k, stride, out_hw, times, grad):
    return {"name": name, "scope": scope, "kind": "conv", "cin": cin,
            "cout": cout, "k": k, "stride": stride, "out_hw": list(out_hw),
            "times": times, "grad": grad}


def dense(name, scope, cin, cout, times, grad="both"):
    return {"name": name, "scope": scope, "kind": "dense", "cin": cin,
            "cout": cout, "times": times, "grad": grad}


def forward_flops(layer: Dict) -> float:
    """One forward application of ``layer``."""
    if "flops" in layer:
        return float(layer["flops"])
    kind = layer.get("kind")
    if kind == "conv":
        oh, ow = layer["out_hw"]
        k = layer["k"]
        return 2.0 * k * k * layer["cin"] * layer["cout"] * oh * ow
    if kind == "dense":
        return 2.0 * layer["cin"] * layer["cout"]
    if kind == "roialign":
        ph, pw = layer["out_hw"]
        # 4 taps, a multiply-add each, at ratio^2 sample points a bin
        return 2.0 * 4 * layer["ratio"] ** 2 * ph * pw * layer["c"]
    raise ValueError(f"layer {layer.get('name')!r}: unknown kind {kind!r} "
                     "and no flops of its own")


def forward_bytes(layer: Dict, width: int = 2) -> float:
    """Bytes one forward application has to move: input, output, weights,
    at ``width`` bytes an element (bf16 activations); a row's own ``bytes``
    as it states them."""
    if "bytes" in layer:
        return float(layer["bytes"])
    kind = layer.get("kind")
    if kind == "conv":
        oh, ow = layer["out_hw"]
        s, k = layer["stride"], layer["k"]
        return width * (oh * s * ow * s * layer["cin"]
                        + oh * ow * layer["cout"]
                        + k * k * layer["cin"] * layer["cout"])
    if kind == "dense":
        return width * (layer["cin"] + layer["cout"]
                        + layer["cin"] * layer["cout"])
    if kind == "roialign":
        ph, pw = layer["out_hw"]
        return width * 5 * layer["ratio"] ** 2 * ph * pw * layer["c"]
    raise ValueError(f"layer {layer.get('name')!r}: unknown kind {kind!r} "
                     "and no bytes of its own")


def step_flops_per_image(layers: List[Dict], scope: str = None) -> float:
    """Forward + backward operations for one sample (an image with its
    ROIs, a sequence with its tokens), of every layer or of those under
    ``scope``."""
    return sum(forward_flops(l) * PASSES[l["grad"]] * l["times"]
               for l in layers if scope is None or l["scope"] == scope)


def least_seconds_per_image(layers: List[Dict], peak: Dict, scope: str
                            ) -> Tuple[float, str]:
    """Least time the chip could take for ``scope``'s layers of one sample,
    layer by layer the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two bounds most of it.  Weights shared
    by the samples of a batch are counted once per sample (an upper count
    of bytes; the detectors' layers are compute-bound all the same)."""
    by_flops = by_bytes = total = 0.0
    for l in layers:
        if l["scope"] != scope:
            continue
        n = PASSES[l["grad"]] * l["times"]
        tf = forward_flops(l) * n / peak["flops_per_s"]
        tb = forward_bytes(l) * n / peak["bytes_per_s"]
        total += max(tf, tb)
        by_flops += tf
        by_bytes += tb
    return total, ("compute" if by_flops >= by_bytes else "memory")
