"""``paddle.Model`` — the Keras-like high-level API.

Reference: ``python/paddle/hapi/model.py`` (SURVEY.md §2.1 hapi, §3.2 call
stack). The reference has DynamicGraphAdapter/StaticGraphAdapter; here the
"static" adapter is a whole-graph jitted train step (XLA is the graph
engine), selected automatically when the model/loss are jit-traceable and
falling back to the eager tape otherwise.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..core.tensor import Tensor
from ..enforce import InvalidArgumentError
from ..framework.io import load as _load
from ..framework.io import save as _save
from ..metric import Metric
from ..observability import metrics as _obs
from ..profiler import _hooks
from .callbacks import config_callbacks

__all__ = ["Model"]


def _as_tensor_batch(data):
    """Host batch -> device Tensors. All host arrays ride ONE device_put
    (a transfer per batch element adds up fast)."""
    import jax

    items = list(data) if isinstance(data, (list, tuple)) else [data]
    host_idx, host_arrs = [], []
    for i, d in enumerate(items):
        if isinstance(d, Tensor):
            continue
        host_idx.append(i)
        host_arrs.append(np.asarray(d))
    if host_idx:
        from ..core.place import device_for_place, expected_place

        # honour paddle.set_device like to_tensor does
        put = jax.device_put(host_arrs, device_for_place(expected_place()))
        for i, v in zip(host_idx, put):
            items[i] = Tensor(v, stop_gradient=True)
    return items


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self.stop_training = False
        self._fused_step = None
        self._fused_failed = False

    # -- setup ---------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        if metrics is None:
            self._metrics = []
        elif isinstance(metrics, Metric):
            self._metrics = [metrics]
        else:
            self._metrics = list(metrics)
        # the compiled steps bake in the loss AND the fused metric set —
        # re-preparing must rebuild them (a stale program would feed one
        # metric's fused result into another)
        self._fused_step = None
        self._fused_failed = False
        self._fused_train_sigs = set()  # compile-window bookkeeping follows
        # the step program it belongs to (stale sigs would skip the
        # fallback-eligible compile window for a rebuilt step)
        self._fused_eval = None
        self._fused_eval_failed = False
        self._fused_pre_counts = [0] * len(self._metrics)
        self._fused_eval_counts = [0] * len(self._metrics)
        return self

    # -- single-batch ops ----------------------------------------------------
    def _traced_metric_flags(self):
        return [getattr(m, "compute_traced", None) is not None
                for m in self._metrics]

    def _collect_traced_pres(self, outs, largs, counts_attr):
        """Run each fused metric's compute_traced during tracing; results
        flatten into the program outputs and the per-metric counts are
        recorded (trace-time side effect, set before the first call
        returns) so the consumer can regroup them."""
        pres, counts = [], []
        for m, f in zip(self._metrics, self._traced_metric_flags()):
            if not f:
                counts.append(0)
                continue
            pre = m.compute_traced(*outs, *largs)
            pre = list(pre) if isinstance(pre, (list, tuple)) else [pre]
            counts.append(len(pre))
            pres.extend(pre)
        setattr(self, counts_attr, counts)
        return pres

    def _finish_fused(self, stepped, labels, counts):
        """Unpack a fused program's (loss, *outs, *pres) result: ONE
        device->host round trip for the loss scalar and every fused metric
        result together. Runs OUTSIDE any eager-fallback window — by the
        time this is called the program's effects are committed, so a
        failure here must propagate, never re-run the batch."""
        import jax

        loss, *rest = stepped
        n_pre = sum(counts)
        outs = rest[:len(rest) - n_pre] if n_pre else rest
        pres = rest[len(rest) - n_pre:] if n_pre else []
        outputs = outs if len(outs) > 1 else outs[0]
        host = jax.device_get([loss._value] + [p._value for p in pres])
        metrics = self._update_metrics(outputs, labels,
                                       fused_pre=host[1:],
                                       fused_counts=counts)
        return (([float(host[0])], metrics) if metrics
                else [float(host[0])])

    def _compute_loss(self, outputs, labels):
        if self._loss is None:
            raise InvalidArgumentError("Model.prepare(loss=...) was not called")
        outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
        labs = labels if isinstance(labels, (list, tuple)) else [labels]
        if callable(self._loss) and not hasattr(self._loss, "forward"):
            return self._loss(*outs, *labs)
        return self._loss(*outs, *labs)

    def _record_train_step(self, t0_ns: int, inputs, loss_val) -> None:
        """Telemetry for one optimizer step (ISSUE 5): step-time histogram
        + samples/s + loss gauges, and a host span in the profiler
        timeline. Runs AFTER the loss fetch that already ended the step —
        every input is a host value, so this adds zero device syncs."""
        t1_ns = _hooks.now_ns()
        _hooks.emit("hapi.train_batch", t0_ns, t1_ns, kind="train")
        dt = (t1_ns - t0_ns) / 1e9
        _obs.histogram("train.step_time_s").observe(dt)
        _obs.counter("train.steps").inc()
        if loss_val is not None:
            _obs.gauge("train.loss").set(float(loss_val))
        try:
            bs = int(inputs[0].shape[0]) if inputs else 0
        except Exception:
            bs = 0
        if bs and dt > 0:
            _obs.gauge("train.samples_per_s").set(bs / dt)

    def train_batch(self, inputs, labels=None, update=True):
        t0_ns = _hooks.now_ns()
        self.network.train()
        inputs = _as_tensor_batch(inputs)
        labels = _as_tensor_batch(labels) if labels is not None else []
        no_pending_grads = self._optimizer is None or all(
            p.grad is None for p in self._optimizer._params())
        if update and self._optimizer is not None and no_pending_grads:
            # hot path: fwd+bwd+optimizer as ONE compiled XLA program per
            # batch (paddle.jit.fused_train_step) — the reference's per-op
            # C++ dispatch has ~ns overhead, ours is a device dispatch, so
            # batching the whole step into one program is the TPU-native
            # equivalent. Falls back to eager per-op if tracing fails.
            if self._fused_step is None and not self._fused_failed:
                net, n_in = self.network, len(inputs)

                # metrics providing compute_traced fuse INTO the step: only
                # their (small) pre-computed results cross to the host per
                # batch, not the full output logits
                def _loss_and_outs(*args):
                    outputs = net(*args[:n_in])
                    loss = self._compute_loss(outputs, list(args[n_in:]))
                    outs = (list(outputs) if isinstance(outputs,
                                                        (list, tuple))
                            else [outputs])
                    pres = self._collect_traced_pres(
                        outs, list(args[n_in:]), "_fused_pre_counts")
                    return (loss, *outs, *pres)

                from ..jit import fused_train_step

                self._fused_step = fused_train_step(
                    _loss_and_outs, self._optimizer, model=self.network,
                    has_aux=True)
            if self._fused_step is not None:
                # fallback window covers ONLY trace/compile: compile() does
                # not execute, donate buffers, or advance optimizer state,
                # so falling back to eager after it fails re-runs nothing.
                # Genuine runtime errors from the compiled call propagate —
                # after donation the eager re-run would read invalidated
                # arrays and apply the gradient twice (ADVICE r2). The
                # compile window runs once per input signature (the
                # signature check is a tuple build + set lookup, keeping the
                # per-batch hot path at ONE _prepare, not two).
                sig = (tuple((tuple(t.shape), str(t.dtype))
                             for t in (*inputs, *labels)),
                       tuple(id(p) for p in self._optimizer._params()))
                seen = self.__dict__.setdefault("_fused_train_sigs", set())
                compiled = sig in seen
                if not compiled:
                    try:
                        self._fused_step.compile(*inputs, *labels)
                        seen.add(sig)
                        compiled = True
                    except Exception as e:
                        self._fused_step = None
                        self._fused_failed = True  # eager from now on
                        import logging

                        logging.getLogger("paddle_tpu.hapi").warning(
                            "fused train step failed to trace/compile; "
                            "falling back to eager per-op execution: %r", e)
                if compiled:
                    stepped = self._fused_step(*inputs, *labels)
                    # post-step work stays OUTSIDE the fallback window: the
                    # optimizer update already committed, so a failure here
                    # must propagate rather than re-run the batch eagerly
                    # (which would apply the gradient twice)
                    res = self._finish_fused(
                        stepped, labels,
                        getattr(self, "_fused_pre_counts",
                                [0] * len(self._metrics)))
                    losses = res[0] if isinstance(res, tuple) else res
                    self._record_train_step(t0_ns, inputs, losses[0])
                    return res
        outputs = self.network(*inputs)
        loss = self._compute_loss(outputs, labels)
        loss.backward()
        if update and self._optimizer is not None:
            self._optimizer.step()
            self._optimizer.clear_grad()
        metrics = self._update_metrics(outputs, labels)
        loss_f = float(loss.item())
        if update and self._optimizer is not None:
            self._record_train_step(t0_ns, inputs, loss_f)
        return ([loss_f], metrics) if metrics else [loss_f]

    def eval_batch(self, inputs, labels=None):
        from ..core.autograd import no_grad

        self.network.eval()
        inputs = _as_tensor_batch(inputs)
        labels = _as_tensor_batch(labels) if labels is not None else []
        # same fusion as train_batch: forward+loss+traced metrics as ONE
        # compiled program, loss + metric results on ONE device_get; only
        # the program CALL may fall back (metric updates must never run
        # twice for one batch, so unpack/update stay outside the window)
        if not getattr(self, "_fused_eval_failed", False):
            stepped = None
            try:
                if getattr(self, "_fused_eval", None) is None:
                    from ..jit import to_static

                    net, n_in = self.network, len(inputs)

                    def _eval_fn(*args):
                        outputs = net(*args[:n_in])
                        loss = self._compute_loss(outputs, list(args[n_in:]))
                        outs = (list(outputs) if isinstance(outputs,
                                                            (list, tuple))
                                else [outputs])
                        pres = self._collect_traced_pres(
                            outs, list(args[n_in:]), "_fused_eval_counts")
                        return (loss, *outs, *pres)

                    self._fused_eval = to_static(_eval_fn, full_graph=False)
                stepped = self._fused_eval(*inputs, *labels)
            except Exception as e:
                self._fused_eval = None
                self._fused_eval_failed = True
                import logging

                logging.getLogger("paddle_tpu.hapi").warning(
                    "fused eval step failed; falling back to eager "
                    "per-op execution: %r", e)
            if stepped is not None:
                return self._finish_fused(
                    stepped, labels,
                    getattr(self, "_fused_eval_counts",
                            [0] * len(self._metrics)))
        with no_grad():
            outputs = self.network(*inputs)
            loss = self._compute_loss(outputs, labels)
        metrics = self._update_metrics(outputs, labels)
        return ([float(loss.item())], metrics) if metrics else [float(loss.item())]

    def predict_batch(self, inputs):
        from ..core.autograd import no_grad

        self.network.eval()
        inputs = _as_tensor_batch(inputs)
        # compiled forward (one program per batch, like train/eval); the
        # outputs are fetched anyway, so only the dispatch count changes
        if not getattr(self, "_fused_pred_failed", False):
            try:
                if getattr(self, "_fused_pred", None) is None:
                    from ..jit import to_static

                    self._fused_pred = to_static(self.network,
                                                 full_graph=False)
                with no_grad():  # inference: skip the program-level vjp
                    outputs = self._fused_pred(*inputs)
                outs = (outputs if isinstance(outputs, (list, tuple))
                        else [outputs])
                return [o.numpy() for o in outs]
            except Exception as e:
                self._fused_pred = None
                self._fused_pred_failed = True
                import logging

                logging.getLogger("paddle_tpu.hapi").warning(
                    "fused predict failed; falling back to eager "
                    "per-op execution: %r", e)
        with no_grad():
            outputs = self.network(*inputs)
        outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
        return [o.numpy() for o in outs]

    def _update_metrics(self, outputs, labels, fused_pre=(), fused_counts=()):
        results = []
        outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
        pre_list = list(fused_pre)
        for i, m in enumerate(self._metrics):
            c = fused_counts[i] if i < len(fused_counts) else 0
            if c:
                pre = [pre_list.pop(0) for _ in range(c)]
            else:
                pre = m.compute(*outs, *labels)
                if not isinstance(pre, (list, tuple)):
                    pre = [pre]
            m.update(*pre)
            results.append(m.accumulate())
        return results

    # -- loops ---------------------------------------------------------------
    def _build_loader(self, data, batch_size, shuffle, num_workers):
        from ..io import DataLoader, Dataset

        if isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers)
        return data  # iterable of batches

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None):
        loader = self._build_loader(train_data, batch_size, shuffle, num_workers)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbks = config_callbacks(
            callbacks, model=self, epochs=epochs, steps=steps,
            log_freq=log_freq, verbose=verbose, save_freq=save_freq,
            save_dir=save_dir, metrics=self._metric_names(),
        )
        self.stop_training = False
        cbks.on_train_begin()
        it = 0
        for epoch in range(epochs):
            cbks.on_epoch_begin(epoch)
            for m in self._metrics:
                m.reset()
            logs = {}
            for step, batch in enumerate(loader):
                cbks.on_train_batch_begin(step)
                inputs, labels = self._split_batch(batch)
                update = (step + 1) % accumulate_grad_batches == 0
                res = self.train_batch(inputs, labels, update=update)
                logs = self._make_logs(res)
                cbks.on_train_batch_end(step, logs)
                it += 1
                if num_iters is not None and it >= num_iters:
                    break
            cbks.on_epoch_end(epoch, logs)
            if eval_data is not None and (epoch + 1) % eval_freq == 0:
                self.evaluate(eval_data, batch_size=batch_size, verbose=0,
                              num_workers=num_workers, callbacks=cbks)
            if self.stop_training or (num_iters is not None and it >= num_iters):
                break
        cbks.on_train_end(logs)
        for c in cbks.callbacks:
            if type(c).__name__ == "History":
                return c.history
        return None

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        loader = self._build_loader(eval_data, batch_size, False, num_workers)
        own_cbks = callbacks is None
        if own_cbks:
            callbacks = config_callbacks(
                None, model=self, verbose=verbose, log_freq=log_freq,
                metrics=self._metric_names(),
            )
        for m in self._metrics:
            m.reset()
        callbacks.on_eval_begin()
        logs = {}
        for step, batch in enumerate(loader):
            callbacks.on_eval_batch_begin(step)
            inputs, labels = self._split_batch(batch)
            res = self.eval_batch(inputs, labels)
            logs = self._make_logs(res)
            callbacks.on_eval_batch_end(step, logs)
        callbacks.on_eval_end(logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=False,
                verbose=1, callbacks=None):
        loader = self._build_loader(test_data, batch_size, False, num_workers)
        outputs = []
        for batch in loader:
            inputs, _ = self._split_batch(batch, has_labels=False)
            outputs.append(self.predict_batch(inputs))
        if stack_outputs:
            n_out = len(outputs[0])
            return [np.concatenate([o[i] for o in outputs]) for i in range(n_out)]
        return outputs

    def _split_batch(self, batch, has_labels=True):
        if isinstance(batch, (list, tuple)):
            if has_labels and len(batch) >= 2:
                return list(batch[:-1]), [batch[-1]]
            return list(batch), []
        return [batch], []

    def _make_logs(self, res):
        logs = {}
        if isinstance(res, tuple):
            losses, metrics = res
            logs["loss"] = losses[0] if len(losses) == 1 else losses
            for m, v in zip(self._metrics, metrics):
                names = m.name()
                logs[names if isinstance(names, str) else names[0]] = v
        else:
            logs["loss"] = res[0] if len(res) == 1 else res
        return logs

    def _metric_names(self):
        names = ["loss"]
        for m in self._metrics:
            n = m.name()
            names.extend([n] if isinstance(n, str) else n)
        return names

    # -- persistence ---------------------------------------------------------
    def save(self, path, training=True):
        _save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            _save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        state = _load(path + ".pdparams") if not path.endswith(".pdparams") else _load(path)
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and os.path.exists(opt_path):
            self._optimizer.set_state_dict(_load(opt_path))

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def summary(self, input_size=None, dtype=None):
        total = sum(p.size for p in self.network.parameters())
        trainable = sum(p.size for p in self.network.parameters() if not p.stop_gradient)
        lines = [repr(self.network), f"Total params: {total:,}",
                 f"Trainable params: {trainable:,}"]
        text = "\n".join(lines)
        print(text)
        return {"total_params": total, "trainable_params": trainable}
