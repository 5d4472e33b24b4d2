"""Entry ``gluon_train_step``: ``parallel.gluon_step.GluonTrainStep`` over a
``{"dp": n}`` mesh, the whole step as one donated XLA program.

Traffic parameters: ``mesh``, ``global_batch``, ``compute_dtype``,
``steps_per_fetch``, ``traced_groups``.

The system's side of the correctness check costs one more step program: a
float32 ``GluonTrainStep`` on the same model and mesh at the check batch,
built and run at highest matmul precision.  With zero initial momentum one
step gives ``W1 = W0 - lr (g + wd W0)``, so ``g = (W0 - W1) / lr - wd W0``:
the gradient of the program's own backward pass and of its own update rule.
"""

import numpy as np

import gluon_model
from benchmark.harness.train_window import TrainSession


def build(ctx):
    return Session(ctx)


class Session(TrainSession):
    def __init__(self, ctx):
        from mxnet_tpu import gluon
        from mxnet_tpu.parallel.gluon_step import GluonTrainStep
        from mxnet_tpu.parallel.mesh import create_mesh

        self.ctx = ctx
        cfg, traffic = ctx.config, ctx.traffic
        self.batch = int(traffic["global_batch"])
        self.steps_per_fetch = int(traffic["steps_per_fetch"])
        self.traced_groups = int(traffic["traced_groups"])
        train = cfg["training"]
        mesh = create_mesh(dict(traffic["mesh"]), devices=ctx.devices)
        self.net = gluon_model.build_net(cfg, ctx.seed)
        self._make_step = lambda dtype: GluonTrainStep(
            self.net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
            lr=train["lr"], momentum=train["momentum"], wd=train["wd"],
            compute_dtype=dtype)
        self.x_check, self.y_check = gluon_model.check_batch(cfg, ctx.seed)
        self.logits = gluon_model.predict_logits(self.net, self.x_check)
        self.step = None

    # ------------------------------------------------------------- check
    def system_outputs(self, reference):
        """Logits, loss and gradients from the program, on the check
        batch, with what the reference needs to compute the same."""
        import jax

        train = self.ctx.config["training"]
        arch = self.ctx.config["architecture"]
        params = gluon_model.named_params(self.net)
        lr, wd = train["lr"], train["wd"]
        with jax.default_matmul_precision("highest"):
            step = self._make_step(None)
            # the step donates its parameters: keep a copy (one program)
            before = jax.jit(lambda vals: [v * 1 for v in vals])(
                step.train_vals)
            masks = gluon_model.replay_dropout(
                self.net, reference.dropout_shapes(arch, len(self.x_check)))
            loss = float(np.asarray(step(self.x_check, self.y_check)))
            grads = jax.jit(lambda w0s, w1s: [
                (w0 - w1) / lr - wd * w0 for w0, w1 in zip(w0s, w1s)])(
                before, step.train_vals)
        names = gluon_model.trainable_names(self.net)
        return {"params": params, "x": self.x_check, "y": self.y_check,
                "dropout_masks": masks, "logits": self.logits, "loss": loss,
                "gradients": dict(zip(names, grads))}

    # ------------------------------------------------------------ window
    def warm_up(self):
        import jax

        shape = (self.batch,) + tuple(self.ctx.config["input"]["shape"])
        classes = self.ctx.config["architecture"]["classes"]
        self.step = self._make_step(self.ctx.traffic["compute_dtype"])

        def batch(key):
            kx, ky = jax.random.split(key)
            return (jax.random.uniform(kx, shape, np.float32),
                    jax.random.randint(ky, (self.batch,), 0, classes,
                                       np.int32))

        self.x, self.y = jax.jit(
            batch, out_shardings=(self.step.batch_sharding,
                                  self.step.label_sharding))(
            jax.random.PRNGKey(self.ctx.seed))
        first = self.fetch(self.dispatch())     # compiles or loads
        self.ctx.say("first step: loss %.4f" % first)
        for _ in range(self.steps_per_fetch):
            handle = self.dispatch()
        self.fetch(handle)

    def dispatch(self):
        return self.step(self.x, self.y)

    def fetch(self, handle):
        return float(np.asarray(handle))

    program_counters = staticmethod(gluon_model.program_counters)
