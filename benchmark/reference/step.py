"""The plain reference of a Faster R-CNN training step, and its SGD.

``reference_steps`` follows the first optimizer steps of a run from the
same seed-made weights and the same batches, image block by image block so
that float32 activations fit beside nothing else on the chip, and returns
what the comparison needs: every step's losses, the first gradient as the
optimizer gets it (after the elementwise clip), and the parameters' change.

Key derivation is the recipe's: the step key is ``fold_in(PRNGKey(seed),
step)``, split into an anchor key and an RCNN key; each is split once more
into one key per image of the whole batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import nets, ops


def normalize(images, im_info, means):
    """uint8 RGB -> float32 minus the pixel means, zero beyond each image's
    real height and width."""
    n, h, w, _ = images.shape
    x = images.astype(jnp.float32) - jnp.asarray(means, jnp.float32)
    rows = jnp.arange(h)[None, :, None, None] < im_info[:, 0].reshape(n, 1, 1, 1)
    cols = jnp.arange(w)[None, None, :, None] < im_info[:, 1].reshape(n, 1, 1, 1)
    return jnp.where(rows & cols, x, 0.0)


def block_sums(params, block, net, tr, mm):
    """Unnormalised loss sums of one block of images.

    ``block``: dict of ``images`` (B, H, W, 3) uint8, ``im_info`` (B, 3),
    ``gt_boxes`` (B, G, 4), ``gt_classes`` (B, G), ``gt_valid`` (B, G),
    ``anchor_keys`` and ``roi_keys`` (B keys each) and, where the head has
    dropout, ``drop_masks`` (one (B * rois, width) array a layer, already
    divided by the keep rate).
    Returns ((rpn_nll, rpn_l1, rcnn_l1), rcnn_nll, (rpn_count, rcnn_count)).
    """
    x = normalize(block["images"], block["im_info"], net["pixel_means"])
    feat = nets.backbone(net, params, x, mm)
    cls, box = nets.rpn(params, feat, mm)
    _, fh, fw, _ = feat.shape
    anchors = jnp.asarray(ops.grid_anchors(
        fh, fw, net["feat_stride"], net["anchor_ratios"], net["anchor_scales"]))
    labels, targets, weights = jax.vmap(
        lambda g, v, i, k: ops.anchor_targets(anchors, g, v, i, k, tr))(
            block["gt_boxes"], block["gt_valid"], block["im_info"],
            block["anchor_keys"])
    rpn_nll, rpn_count = ops.nll_sum(cls, labels)
    rpn_l1 = ops.smooth_l1_sum(box, targets, weights, 3.0)

    fg = jax.nn.softmax(jax.lax.stop_gradient(cls), -1)[..., 1]
    rois, valid = jax.vmap(
        lambda s, d, i: ops.proposals(s, d, anchors, i, tr))(
            fg, jax.lax.stop_gradient(box), block["im_info"])
    boxes, roi_labels, roi_targets, roi_weights = jax.vmap(
        lambda r, v, g, c, gv, k: ops.sample_rois(
            r, v, g, c, gv, k, tr, net["num_classes"]))(
                rois, valid, block["gt_boxes"], block["gt_classes"],
                block["gt_valid"], block["roi_keys"])
    pooled = jax.vmap(lambda f, r: ops.roi_align(
        f, r, net["pooled_size"], 1.0 / net["feat_stride"], mm))(feat, boxes)
    logits, deltas = nets.head(
        net, params, pooled.reshape((-1,) + pooled.shape[2:]), mm,
        block.get("drop_masks"))
    rcnn_nll, rcnn_count = ops.nll_sum(logits, roi_labels.reshape(-1))
    rcnn_l1 = ops.smooth_l1_sum(
        deltas, roi_targets.reshape(deltas.shape),
        roi_weights.reshape(deltas.shape), 1.0)
    return (rpn_nll, rpn_l1, rcnn_l1), rcnn_nll, (rpn_count, rcnn_count)


def step_keys(seed, step, n):
    """(anchor keys (n,), roi keys (n,), dropout key) of optimizer step
    ``step`` (0-based) for a batch of ``n`` images."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    k_anchor, k_rcnn = jax.random.split(key)
    k_prop, k_drop = jax.random.split(k_rcnn)
    return jax.random.split(k_anchor, n), jax.random.split(k_prop, n), k_drop


def dropout_masks(net, k_drop, rows):
    """The head's dropout masks of one step over all ``rows`` ROIs, drawn as
    ``flax.linen.Dropout`` draws them: a Bernoulli(keep) on the key flax
    derives for the layer (the SHA-1 of its scope path and the draw count 1,
    folded into the step's dropout key), scaled by 1 / keep."""
    import hashlib

    spec = net.get("dropout")
    if not spec:
        return None
    keep = 1.0 - spec["rate"]
    masks = []
    for scope in spec["scopes"]:
        h = hashlib.sha1()
        for part in scope:
            h.update(part.encode("utf-8"))
        h.update((1).to_bytes(1, "big"))
        key = jax.random.fold_in(
            k_drop, jnp.uint32(int.from_bytes(h.digest()[:4], "big")))
        masks.append(jax.random.bernoulli(key, keep, (rows, spec["width"]))
                     .astype(jnp.float32) / keep)
    return masks


def tree_paths(tree, prefix=()):
    """Flat {path tuple: leaf} of a nested dict."""
    out = {}
    for name, sub in tree.items():
        if isinstance(sub, dict):
            out.update(tree_paths(sub, prefix + (name,)))
        else:
            out[prefix + (name,)] = sub
    return out


def trainable(path, fixed):
    """The recipe's FIXED_PARAMS rule: a parameter is frozen when a scope of
    its path starts with a listed prefix; 'gamma'/'beta' freeze the scale /
    bias of every batch norm."""
    prefixes = tuple(p for p in fixed if p not in ("gamma", "beta"))
    if any(name.startswith(prefixes) for name in path if prefixes):
        return False
    if len(path) > 1 and path[-2].startswith("bn"):
        if "gamma" in fixed and path[-1] == "scale":
            return False
        if "beta" in fixed and path[-1] == "bias":
            return False
    return True


def make_block_grads(net, tr, mm):
    """The jitted loss gradient of one block of images, normalised for a
    batch whose ``counts`` are (RPN labels, RCNN labels, images) over the
    whole batch.  Returns the gradient and the block's own sums and
    counts."""
    @jax.jit
    def block_grads(p, blk, counts):
        def f(p):
            (rpn_nll, rpn_l1, rcnn_l1), rcnn_nll, seen = block_sums(
                p, blk, net, tr, mm)
            loss = (rpn_nll / counts[0]
                    + rpn_l1 / (tr["rpn_batch_size"] * counts[2])
                    + rcnn_l1 / (tr["batch_rois"] * counts[2])
                    + rcnn_nll / counts[1])
            return loss, (jnp.stack([rpn_nll, rpn_l1, rcnn_l1, rcnn_nll]),
                          jnp.stack(seen))
        return jax.grad(f, has_aux=True)(p)

    return block_grads


def label_counts(net, tr, batch, akeys, bucket_hw):
    """RPN labels != -1 over a whole batch: they depend on boxes, anchors
    and the draw alone, so they are known before any network runs."""
    fh, fw = bucket_hw[0] // net["feat_stride"], bucket_hw[1] // net["feat_stride"]
    anchors = jnp.asarray(ops.grid_anchors(
        fh, fw, net["feat_stride"], net["anchor_ratios"], net["anchor_scales"]))
    labels, _, _ = jax.jit(jax.vmap(
        lambda g, v, i, k: ops.anchor_targets(anchors, g, v, i, k, tr)))(
            batch["gt_boxes"], batch["gt_valid"], batch["im_info"], akeys)
    return int((labels >= 0).sum())


def reference_steps(net, tr, opt, params, batches, seed, *, steps, block,
                    precision="float32", skip_half=False, scan=True):
    """Follow ``steps`` optimizer steps from ``params`` over ``batches`` (a
    list of host batches, dicts as ``block_sums`` takes without the keys).

    Returns ``{"losses": [per step dict], "grad_norm": {path: float} of the
    first step's clipped gradient, "grad_norm_any": each leaf's largest
    clipped gradient over the steps followed, "first_delta_norm": {path:
    float} of the parameters' change after the first step, "delta_norm":
    the same after the last step}``.  ``skip_half`` plants the fault of a
    step that leaves out the second half of every batch and takes the mean
    over the rest.
    """
    mm = nets.Contract(precision, scan)
    n = batches[0]["images"].shape[0]
    used = n // 2 if skip_half else n

    block_grads = make_block_grads(net, tr, mm)

    @jax.jit
    def sgd(p, trace, grads):
        def one(path, w, t, g):
            if not trainable(path, opt["fixed_params"]):
                return w, t, jnp.zeros(())
            g = jnp.clip(g, -opt["clip_gradient"], opt["clip_gradient"])
            t = opt["momentum"] * t + g + opt["wd"] * w
            return w - opt["lr"] * t, t, jnp.sqrt(jnp.sum(g * g))
        flat_p, flat_t, flat_g = (tree_paths(x) for x in (p, trace, grads))
        out = {k: one(k, flat_p[k], flat_t[k], flat_g[k]) for k in flat_p}
        return out

    def unflatten(flat, i):
        tree = {}
        for path, v in flat.items():
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = v[i]
        return tree

    p0 = params
    trace = jax.tree.map(jnp.zeros_like, params)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    change = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum((x - y) ** 2)), a, b))
    bucket_hw = batches[0]["images"].shape[1:3]
    losses, grad_norm, grad_norm_any = [], None, None
    for step in range(steps):
        batch = {k: v[:used] for k, v in batches[step].items()}
        akeys, rkeys, k_drop = step_keys(seed, step, n)
        rois = tr["batch_rois"]
        masks = dropout_masks(net, k_drop, n * rois)
        # every ROI slot is filled unless an image yields too few proposals;
        # a step whose counts turn out otherwise is run again with them
        counts = [max(label_counts(net, tr, batch, akeys[:used], bucket_hw), 1),
                  rois * used]
        while True:
            grads, sums, seen = None, np.zeros(4), np.zeros(2, np.int64)
            for lo in range(0, used, block):
                sl = slice(lo, min(lo + block, used))
                blk = {k: jnp.asarray(v[sl]) for k, v in batch.items()}
                blk["anchor_keys"], blk["roi_keys"] = akeys[sl], rkeys[sl]
                if masks is not None:
                    blk["drop_masks"] = [m[sl.start * rois:sl.stop * rois]
                                         for m in masks]
                g, (part, cnt) = block_grads(
                    params, blk, jnp.asarray(counts + [used], jnp.float32))
                grads = g if grads is None else add(grads, g)
                sums += np.asarray(part, np.float64)
                seen += np.asarray(cnt)
            found = [max(int(c), 1) for c in seen]
            if found == counts:
                break
            counts = found
        row = {"rpn_logloss": sums[0] / counts[0],
               "rpn_l1loss": sums[1] / (tr["rpn_batch_size"] * used),
               "rcnn_l1loss": sums[2] / (tr["batch_rois"] * used),
               "rcnn_logloss": sums[3] / counts[1]}
        row["loss"] = sum(row.values())
        losses.append(row)
        out = sgd(params, trace, grads)
        norms = {k: float(v[2]) for k, v in out.items()
                 if trainable(k, opt["fixed_params"])}
        if step == 0:
            grad_norm = grad_norm_any = norms
        grad_norm_any = {k: max(v, grad_norm_any[k])
                         for k, v in norms.items()}
        params, trace = unflatten(out, 0), unflatten(out, 1)
        del grads, out
        if step in (0, steps - 1):
            moved = {k: float(v) for k, v in tree_paths(change(params, p0)
                                                        ).items()
                     if trainable(k, opt["fixed_params"])}
            if step == 0:
                first_delta_norm = moved
            delta_norm = moved
    return {"losses": losses, "grad_norm": grad_norm,
            "grad_norm_any": grad_norm_any,
            "first_delta_norm": first_delta_norm, "delta_norm": delta_norm}
