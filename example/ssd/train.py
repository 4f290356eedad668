#!/usr/bin/env python
"""Single-shot detector (SSD) — BASELINE config #4.

Port of /root/reference/example/ssd/: a conv backbone with multi-scale
heads wired through the contrib MultiBox trio —
MultiBoxPrior (anchors) → MultiBoxTarget (training targets) →
MultiBoxDetection (NMS'd detections at inference).

Runs on a synthetic shapes dataset (bright rectangles of 2 classes on
dark background) when no --data-train .rec is given, so the full
anchor/target/loss/detect pipeline exercises end to end with zero
downloads.
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(os.path.expanduser(__file__))), "..", ".."))
import mxnet_tpu as mx  # noqa: E402


def conv_act(data, name, num_filter, kernel=(3, 3), pad=(1, 1),
             stride=(1, 1)):
    c = mx.sym.Convolution(data=data, kernel=kernel, pad=pad,
                           stride=stride, num_filter=num_filter,
                           name=name)
    b = mx.sym.BatchNorm(data=c, name=name + "_bn")
    return mx.sym.Activation(data=b, act_type="relu", name=name + "_relu")


def multibox_layer(from_layers, num_classes, sizes, ratios):
    """Per-scale cls/loc heads + anchors (reference example/ssd/symbol/
    common.py:multibox_layer)."""
    cls_preds = []
    loc_preds = []
    anchors = []
    for i, layer in enumerate(from_layers):
        size = sizes[i]
        ratio = ratios[i]
        num_anchors = len(size) + len(ratio) - 1
        # location regression head
        loc = mx.sym.Convolution(data=layer, kernel=(3, 3), pad=(1, 1),
                                 num_filter=num_anchors * 4,
                                 name="loc_pred_%d" % i)
        loc = mx.sym.transpose(loc, axes=(0, 2, 3, 1))
        loc_preds.append(mx.sym.Flatten(loc))
        # class prediction head
        cls = mx.sym.Convolution(data=layer, kernel=(3, 3), pad=(1, 1),
                                 num_filter=num_anchors * (num_classes + 1),
                                 name="cls_pred_%d" % i)
        cls = mx.sym.transpose(cls, axes=(0, 2, 3, 1))
        cls_preds.append(mx.sym.Reshape(
            mx.sym.Flatten(cls), shape=(0, -1, num_classes + 1)))
        # anchors
        anc = mx.sym.contrib.MultiBoxPrior(
            layer, sizes=tuple(size), ratios=tuple(ratio), clip=True,
            name="anchors_%d" % i)
        anchors.append(anc)
    loc_preds = mx.sym.Concat(*loc_preds, dim=1, name="multibox_loc_pred")
    cls_preds = mx.sym.Concat(*cls_preds, dim=1, name="multibox_cls_pred")
    cls_preds = mx.sym.transpose(cls_preds, axes=(0, 2, 1))
    anchors = mx.sym.Concat(*anchors, dim=1, name="multibox_anchors")
    return [loc_preds, cls_preds, anchors]


def get_ssd_symbol(num_classes=2, mode="train"):
    """Small SSD: 3 scales over a 5-conv backbone."""
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("label")
    b1 = conv_act(data, "conv1", 16)
    p1 = mx.sym.Pooling(b1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    b2 = conv_act(p1, "conv2", 32)
    p2 = mx.sym.Pooling(b2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    b3 = conv_act(p2, "conv3", 64)          # stride 4 feature map
    p3 = mx.sym.Pooling(b3, pool_type="max", kernel=(2, 2), stride=(2, 2))
    b4 = conv_act(p3, "conv4", 64)          # stride 8
    p4 = mx.sym.Pooling(b4, pool_type="max", kernel=(2, 2), stride=(2, 2))
    b5 = conv_act(p4, "conv5", 64)          # stride 16

    sizes = [[0.2, 0.27], [0.37, 0.45], [0.54, 0.62]]
    ratios = [[1.0, 2.0, 0.5]] * 3
    loc_preds, cls_preds, anchors = multibox_layer(
        [b3, b4, b5], num_classes, sizes, ratios)

    if mode == "train":
        tmp = mx.sym.contrib.MultiBoxTarget(
            anchors, label, cls_preds, overlap_threshold=0.5,
            ignore_label=-1, negative_mining_ratio=3,
            minimum_negative_samples=0, negative_mining_thresh=0.5,
            variances=(0.1, 0.1, 0.2, 0.2), name="multibox_target")
        loc_target, loc_target_mask, cls_target = tmp[0], tmp[1], tmp[2]
        cls_prob = mx.sym.SoftmaxOutput(
            data=cls_preds, label=cls_target,
            ignore_label=-1, use_ignore=True,
            multi_output=True, normalization="valid",
            name="cls_prob")
        loc_diff = loc_target_mask * (loc_preds - loc_target)
        loc_loss_ = mx.sym.smooth_l1(data=loc_diff, scalar=1.0,
                                     name="loc_loss_")
        loc_loss = mx.sym.MakeLoss(loc_loss_, grad_scale=1.0,
                                   normalization="valid",
                                   name="loc_loss")
        cls_label = mx.sym.MakeLoss(data=cls_target, grad_scale=0,
                                    name="cls_label")
        det = mx.sym.contrib.MultiBoxDetection(
            cls_prob, loc_preds, anchors,
            name="detection", nms_threshold=0.45, force_suppress=False,
            variances=(0.1, 0.1, 0.2, 0.2), nms_topk=400)
        det = mx.sym.MakeLoss(data=det, grad_scale=0, name="det_out")
        return mx.sym.Group([cls_prob, loc_loss, cls_label, det])
    # inference
    cls_prob = mx.sym.softmax(data=cls_preds, axis=1)
    return mx.sym.contrib.MultiBoxDetection(
        cls_prob, loc_preds, anchors, name="detection",
        nms_threshold=0.45, variances=(0.1, 0.1, 0.2, 0.2), nms_topk=400)


def synthetic_batch(batch_size, size=64, max_obj=2, seed=0):
    """Images with 1-2 bright rectangles; label rows
    [cls, x1, y1, x2, y2] normalized, padded with -1."""
    rng = np.random.RandomState(seed)
    x = rng.uniform(0, 0.1, (batch_size, 3, size, size)).astype(np.float32)
    y = np.full((batch_size, max_obj, 5), -1.0, np.float32)
    for b in range(batch_size):
        for k in range(rng.randint(1, max_obj + 1)):
            w = rng.uniform(0.25, 0.5)
            h = rng.uniform(0.25, 0.5)
            x1 = rng.uniform(0, 1 - w)
            y1 = rng.uniform(0, 1 - h)
            cls = rng.randint(0, 2)
            px = slice(int(x1 * size), int((x1 + w) * size))
            py = slice(int(y1 * size), int((y1 + h) * size))
            val = 0.9 if cls else 0.5
            x[b, :, py, px] = val
            y[b, k] = [cls, x1, y1, x1 + w, y1 + h]
    return x, y


def write_shapes_rec(path, n=256, size=64, max_obj=2, seed=0):
    """Pack the synthetic shapes dataset into a detection .rec (flat
    labels [2, 5, obj...]) so the NATIVE box-aware pipeline
    (io.ImageDetRecordIter, src/mxtpu/det_aug.cc) can feed training."""
    from mxnet_tpu import recordio
    rng = np.random.RandomState(seed)
    w = recordio.MXRecordIO(path, "w")
    for i in range(n):
        img = rng.uniform(0, 25, (size, size, 3))
        objs = []
        for _ in range(rng.randint(1, max_obj + 1)):
            bw, bh = rng.uniform(0.25, 0.5, 2)
            x1 = rng.uniform(0, 1 - bw)
            y1 = rng.uniform(0, 1 - bh)
            cls = rng.randint(0, 2)
            val = 230 if cls else 128
            img[int(y1 * size):int((y1 + bh) * size),
                int(x1 * size):int((x1 + bw) * size)] = val
            objs.append([float(cls), x1, y1, x1 + bw, y1 + bh])
        flat = np.asarray([2.0, 5.0] + [v for o in objs for v in o],
                          np.float32)
        # pack_img owns the JPEG encode (recordio.py); BGR in, like the
        # reference's cv2 convention — the shapes are channel-symmetric
        w.write(recordio.pack_img(
            recordio.IRHeader(len(flat), flat, i, 0),
            img.astype(np.uint8)[:, :, ::-1], quality=95))
    w.close()


def main():
    parser = argparse.ArgumentParser(description="train a tiny SSD")
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--steps", type=int, default=150)
    parser.add_argument("--lr", type=float, default=0.02)
    parser.add_argument("--num-classes", type=int, default=2)
    parser.add_argument("--image-size", type=int, default=64)
    parser.add_argument("--data-train", default="",
                        help="detection .rec: train through the native "
                             "box-aware pipeline (io.ImageDetRecordIter) "
                             "instead of in-memory synthetic batches; "
                             "'synthetic' writes+uses a generated one")
    args = parser.parse_args()

    rec_iter = None
    if args.data_train:
        rec_path = args.data_train
        if rec_path == "synthetic":
            import tempfile
            rec_path = os.path.join(tempfile.mkdtemp(prefix="ssd_rec_"),
                                    "shapes.rec")
            write_shapes_rec(rec_path, n=32 * args.batch_size,
                             size=args.image_size)
        # the native pipeline decodes/augments on C++ worker threads;
        # mirror is box-aware, pixels normalized to the synthetic scale
        rec_iter = mx.io.ImageDetRecordIter(
            path_imgrec=rec_path,
            data_shape=(3, args.image_size, args.image_size),
            batch_size=args.batch_size, shuffle=True, seed=0,
            rand_mirror=True, std_r=255.0, std_g=255.0, std_b=255.0)
        label_shape = (args.batch_size, rec_iter.max_objects,
                       rec_iter.object_width)
        print("rec-mode: %d samples, label shape %s"
              % (rec_iter.num_samples, label_shape))

    net = get_ssd_symbol(args.num_classes, mode="train")
    mod = mx.mod.Module(net, data_names=("data",), label_names=("label",),
                        context=mx.tpu() if mx.num_gpus() > 0 else mx.cpu())
    x, y = synthetic_batch(args.batch_size, args.image_size)
    if rec_iter is not None:
        y = np.full(label_shape, -1.0, np.float32)
    mod.bind(data_shapes=[("data", x.shape)],
             label_shapes=[("label", y.shape)])
    mod.init_params(mx.init.Xavier(magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": args.lr,
                                         "momentum": 0.9, "wd": 1e-4})
    import time

    def next_batch(step):
        if rec_iter is None:
            xs, ys = synthetic_batch(args.batch_size, args.image_size,
                                     seed=step)
            return mx.io.DataBatch([mx.nd.array(xs)], [mx.nd.array(ys)])
        try:
            return next(rec_iter)
        except StopIteration:
            rec_iter.reset()
            return next(rec_iter)

    accs = []
    for step in range(args.steps):
        batch = next_batch(step)
        t0 = time.time()
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
        if step % 10 == 0:
            cls_prob = mod.get_outputs()[0].asnumpy()
            cls_target = mod.get_outputs()[2].asnumpy()
            mask = cls_target >= 0
            pred = cls_prob.argmax(axis=1)
            acc = (pred[mask[:, :]] == cls_target[mask]).mean() \
                if mask.any() else 0.0
            accs.append(acc)
            print("step %d anchor-cls acc %.3f (%.2fs)"
                  % (step, acc, time.time() - t0))
    # final detection sanity: run the detect head
    det = mod.get_outputs()[3].asnumpy()
    print("detections shape:", det.shape)
    print("best detection per image (cls, score, box):")
    for b in range(min(2, det.shape[0])):
        best = det[b, det[b, :, 1].argmax()]
        print("  img%d:" % b, best)
    if args.steps >= 100:
        # the last five samples, not the one batch that happens to be
        # sampled last: a batch of 8 moves the accuracy by 0.05
        last = float(np.mean(accs[-5:]))
        print("mean anchor-cls acc of the last 5 samples %.3f" % last)
        assert last > 0.75, "SSD anchor classification failed to learn"
    print("SSD OK")


if __name__ == "__main__":
    main()
