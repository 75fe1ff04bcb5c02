"""Forward FLOPs computed from shapes (2 FLOPs per multiply-add).

These count the work a configuration *requires*, independent of how the
program schedules it: the whole-step utilisations (``*.mfu``) divide them
by the chip's peak.  Convolutions, projections and the classifier are
counted; batch norm, activations and pooling (well under 1%) are not.
"""
from __future__ import annotations

from typing import List, Tuple


def cnn_segments(cfg: dict) -> List[Tuple[str, float]]:
    """``[(segment, forward FLOPs per image)]`` of a CIFAR-style ResNet
    (basic blocks) in forward order: ``stem``, ``g<stage>b<block>``,
    ``head``.  ``cfg`` holds ``image_size``, ``stem_channels``,
    ``stages`` ([channels, blocks, stride] each) and ``n_classes``."""
    hw = cfg["image_size"]
    cin = cfg["stem_channels"]
    segs = [("stem", 2.0 * 9 * 3 * cin * hw * hw)]
    for si, (cout, n, stride) in enumerate(cfg["stages"]):
        for bi in range(n):
            s = stride if bi == 0 else 1
            hw //= s
            f = 2.0 * 9 * (cin + cout) * cout * hw * hw
            if s != 1 or cin != cout:
                f += 2.0 * cin * cout * hw * hw
            segs.append((f"g{si}b{bi}", f))
            cin = cout
    segs.append(("head", 2.0 * cin * cfg["n_classes"]))
    return segs


def cnn_forward(cfg: dict) -> float:
    """Forward FLOPs of one image through the whole network."""
    return sum(f for _, f in cnn_segments(cfg))


def cnn_suffix(cfg: dict) -> dict:
    """segment -> forward FLOPs per image from that segment to the head."""
    segs = cnn_segments(cfg)
    out, tail = {}, 0.0
    for name, f in reversed(segs):
        tail += f
        out[name] = tail
    return out

