"""Device-side ops: the TPU-native replacements for the reference's
Cython/CUDA kernels (``rcnn/cython/``), NumPy geometry
(``rcnn/processing/``) and CustomOp graph layers (``rcnn/symbol/proposal*``,
``rcnn/io/rpn.py``, ``rcnn/io/rcnn.py``).

Everything in this package is pure-functional jnp, shape-static, and safe
inside ``jax.jit`` — one XLA program per training step, no host bounces.
"""

from mx_rcnn_tpu.ops.anchors import generate_anchors, generate_shifted_anchors  # noqa: F401
from mx_rcnn_tpu.ops.boxes import (  # noqa: F401
    bbox_overlaps,
    bbox_transform,
    bbox_pred,
    clip_boxes,
)
from mx_rcnn_tpu.ops.nms import (nms, nms_batch, nms_mask,  # noqa: F401
                                 nms_mask_batch)
from mx_rcnn_tpu.ops.proposal import propose, propose_batch  # noqa: F401
from mx_rcnn_tpu.ops.roi_pool import (roi_align, roi_align_batched,  # noqa: F401
                                      roi_pool)
from mx_rcnn_tpu.ops.targets import anchor_target, proposal_target  # noqa: F401
from mx_rcnn_tpu.ops.losses import (  # noqa: F401
    smooth_l1,
    softmax_cross_entropy_with_ignore,
)
