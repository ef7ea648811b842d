"""Task abstraction: the model/data pair is a first-class sweep axis.

Before this layer the federated stack hard-coded the paper's experimental
model — ``models/mlp.py`` on synthetic-MNIST — into every plane: the
padded cohort engine trained ``(K, S, 784)/(K, S)`` feature/label arrays
through ``mlp_sgd_epoch_masked``, the server's quality statistics were
label histograms, and evaluation meant class-masked test accuracy. A
``FeelTask`` generalizes that contract so DQS scheduling (Eq. 1-3,
Alg. 1/2) runs unchanged over ANY pytree of batchable per-sample arrays
and ANY pytree model:

    data plane  — generate / partition / histogram / gini: the task owns
        its dataset type (``Dataset`` / ``TokenDataset``), the group-based
        non-IID allocation constants, and the metadata a UE reports (class
        histogram for MNIST; token histogram for the LM — quality is
        measured on what the model LEARNS, not the partition sort key).
    device plane — init_params / sgd_epoch / local_metric /
        predict_units / eval_loss: jit-static methods (tasks are frozen,
        hashable dataclasses) the cohort engine vmaps over the client
        axis. The padded/masked contract is unchanged: zero-padded rows
        with mask 0 contribute exactly zero gradient.
    eval units  — the task defines the atomic prediction "unit" the
        reputation machinery scores: MNIST units are test SAMPLES, LM
        units are the ``W x (seq-1)`` next-token TARGET POSITIONS of the
        held-out windows. Per-UE support masks (Eq. 1's class-restricted
        acc_test, DESIGN.md §2) become unit masks via each UE's claimed
        histogram; the watched (source, target) attack metrics ride on
        units too, so ``attack_success`` means "fraction of watched
        source-token positions decoded as the attack's target token" for
        the LM — the exact analogue of the MNIST definition. Masked unit
        accuracies are sums of {0,1} float32 counts (< 2^24), so subset
        and masked-full evaluations agree bit-for-bit.
    loop oracle — local_train / eval_units_loop / global_metrics: the
        sequential host paths (``engine="loop"``, ``control="host"``)
        each task keeps as its parity oracle; the MNIST task delegates to
        the exact pre-refactor code (``federated.client.local_train``,
        ``models.mlp``), which is what pins the refactor to the golden
        curves.

``TASKS`` registers three concrete tasks:

    mnist_mlp — the paper's §V protocol, bit-parity with the
        pre-task-abstraction stack.
    lm_tiny   — federated fine-tuning of a 2-layer decoder-only
        transformer (``models/transformer.py`` through the shared blocks
        stack, so ``REPRO_USE_PALLAS=1`` routes its attention through the
        Pallas flash kernel) on synthetic domain-skewed token windows
        (``data/tokens.py``). Clients hold fixed-length windows from a
        Zipf-Markov stream; domains play the non-IID role of MNIST
        labels for the partition, while quality statistics and eval
        masks are computed over the TOKENS.
    moonlight_ft — the same task on one chip's share of Moonlight-16B-A3B
        (``configs/moonlight_16b_a3b.py::ONE_CHIP``: MLA, 8 of 64 routed
        experts held, the vocabulary's first eighth) at its published
        widths: windows of 2,048 tokens, one window a local step, each
        layer rematerialised in the backward pass. Too large to stack,
        its cohort trains streamed (federated/cohort.py).

The LM tasks also expose ``sgd_step``, one local step on one batch, which
the streamed cohort path drives client by client.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.moonlight_16b_a3b import ONE_CHIP
from repro.core.diversity import gini_simpson, gini_simpson_hist
from repro.data.partition import (GROUP_SIZE, MAX_GROUPS, MIN_GROUPS,
                                  label_histogram, partition)
from repro.data.synthetic_mnist import N_CLASSES, generate
from repro.data.tokens import make_windows
from repro.federated.client import ClientReport, local_train
from repro.models.common import count_accuracy
from repro.models.mlp import (mlp_accuracy, mlp_apply, mlp_correct_counts,
                              mlp_init, mlp_sgd_epoch_masked)
from repro.models.transformer import (lm_correct_counts, lm_forward,
                                      lm_init, lm_loss, lm_sgd_epoch,
                                      lm_sgd_epoch_masked, lm_sgd_step)


class FeelTask:
    """Interface every task implements (see module docstring).

    Tasks are frozen dataclasses: hashable and eq-comparable, so they pass
    through ``jax.jit`` as static arguments and key compile caches — two
    servers configured with the same task share every compiled cohort
    program.

    Host/data plane:  generate_data, partition_clients, histogram, gini.
    Eval units:       unit_labels, unit_rows, eval_inputs, unit_targets.
    Device plane:     init_params, sgd_epoch, local_metric, predict_units,
                      eval_loss (None when the task has no loss metric);
                      sgd_step where the cohort can stream (LmTask).
    Loop oracle:      local_train, eval_units_loop, global_metrics.
    Protocol knobs:   group_size/min_groups/max_groups (partition),
                      batch_size, default_lr, default_n_train/_n_test.
    """

    name: str


@dataclasses.dataclass(frozen=True)
class MnistTask(FeelTask):
    """The paper's §V protocol: 2-layer MLP on synthetic MNIST.

    Every method delegates to the exact pre-task-abstraction code path
    (``models/mlp.py``, ``federated/client.py``, ``data/partition.py``
    defaults), which is what keeps the refactored stack bit-identical to
    the golden curves recorded before the task layer existed.
    """
    name: str = "mnist_mlp"
    n_symbols: int = N_CLASSES
    group_size: int = GROUP_SIZE
    min_groups: int = MIN_GROUPS
    max_groups: int = MAX_GROUPS
    batch_size: int = 50
    default_lr: float = 0.1
    default_n_train: int = 50_000
    default_n_test: int = 10_000

    # -- host/data plane ------------------------------------------------ #
    def generate_data(self, n_train: int, n_test: int, seed: int):
        return generate(n_train, n_test, seed=seed)

    def partition_clients(self, train, n_ues, rng, malicious=None,
                          attack=None, context=""):
        return partition(train, n_ues, rng, malicious, attack,
                         group_size=self.group_size,
                         min_groups=self.min_groups,
                         max_groups=self.max_groups,
                         context=context or f"task={self.name}")

    def histogram(self, data) -> np.ndarray:
        """What a UE reports: its label histogram (claimed class support)."""
        return label_histogram(data, self.n_symbols)

    def gini(self, data) -> float:
        """Eq. 2 elements diversity: Gini-Simpson over label frequencies."""
        return gini_simpson(data.y, self.n_symbols)

    # -- eval units (host) ----------------------------------------------- #
    def unit_labels(self, test) -> np.ndarray:
        return np.asarray(test.y)

    def unit_rows(self, test) -> np.ndarray:
        return np.arange(len(test.y))

    def eval_inputs(self, test):
        return {"x": jnp.asarray(test.x)}

    def unit_targets(self, test):
        return jnp.asarray(test.y)

    # -- device plane (static under jit) ---------------------------------- #
    def init_params(self, key):
        return mlp_init(key)

    def sgd_epoch(self, params, d, m, lr, batch_size: int):
        return mlp_sgd_epoch_masked(params, d["x"], d["y"], m, lr,
                                    batch_size)

    def local_metric(self, params, d, m):
        return mlp_correct_counts(params, d["x"], d["y"], m)

    def predict_units(self, params, ei):
        return jnp.argmax(mlp_apply(params, ei["x"]), -1)

    def eval_loss(self, params, ei):
        return None          # accuracy is the task's only global metric

    # -- loop oracle (host) ------------------------------------------------ #
    def local_train(self, client, global_params, epochs: int, lr: float,
                    batch_size: int) -> ClientReport:
        return local_train(client, global_params, epochs, lr,
                           batch_size=batch_size)

    def eval_units_loop(self, params, test, m: np.ndarray) -> float:
        if not m.any():
            return 0.0
        return float(mlp_accuracy(params, jnp.asarray(test.x[m]),
                                  jnp.asarray(test.y[m])))

    def global_metrics(self, params, test, ei, ey, watch_class,
                       watch_target):
        """(global_acc, global_loss, source_acc, attack_success)."""
        g_acc = float(mlp_accuracy(params, ei["x"], ey))
        src_acc = atk_succ = float("nan")
        if watch_class is not None:
            m = test.y == watch_class
            if m.any():
                xs = jnp.asarray(test.x[m])
                src_acc = float(mlp_accuracy(
                    params, xs, jnp.asarray(test.y[m])))
                if watch_target is not None:
                    tgt = jnp.full(int(m.sum()), watch_target, ey.dtype)
                    atk_succ = float(mlp_accuracy(params, xs, tgt))
        return g_acc, float("nan"), src_acc, atk_succ


# 2-layer decoder-only transformer, small enough that a full federated
# sweep runs in seconds yet large enough to learn the Zipf-Markov bigram
# structure. seq=32 is a multiple of 8, so with REPRO_USE_PALLAS=1 its
# attention dispatches to the Pallas flash kernel (models/attention.py).
LM_TINY = ModelConfig(name="lm-tiny", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=64, dtype="float32")


@partial(jax.jit, static_argnums=0)
def _lm_init(cfg, key):
    return lm_init(key, cfg)


@partial(jax.jit, static_argnums=0)
def _lm_eval_loss(cfg, params, tokens):
    return lm_loss(cfg, params, {"tokens": tokens})[0]


@partial(jax.jit, static_argnums=0)
def _lm_predict(cfg, params, tokens):
    """(W, S) tokens -> (W*(S-1),) greedy next-token predictions (units)."""
    logits, _, _, _ = lm_forward(cfg, params, tokens,
                                 window=cfg.sliding_window)
    return jnp.argmax(logits[:, :-1], -1).reshape(-1)


@dataclasses.dataclass(frozen=True)
class LmTask(FeelTask):
    """Federated LM fine-tuning on synthetic domain-skewed token windows.

    Clients hold ``(n, seq)`` int32 windows cut from per-domain Zipf-Markov
    streams (``data/tokens.py::make_windows``); the window's domain id is
    the partition sort key (the non-IID role MNIST labels play), while the
    server-visible quality metadata — histogram, Gini-Simpson diversity,
    eval support masks — is computed over the TOKENS the model actually
    learns. Evaluation units are the held-out windows' next-token target
    positions; ``eval_loss`` adds the held-out per-token cross-entropy as
    the global quality metric (RoundLog.global_loss). ``remat``
    rematerialises each layer in local training's backward pass.
    """
    name: str = "lm_tiny"
    model: ModelConfig = LM_TINY
    seq: int = 32
    n_domains: int = 10
    group_size: int = 16
    min_groups: int = 1
    max_groups: int = 8
    batch_size: int = 8
    default_lr: float = 0.3
    default_n_train: int = 2_000
    default_n_test: int = 400
    remat: bool = False

    @property
    def n_symbols(self) -> int:
        return self.model.vocab_size

    # -- host/data plane ------------------------------------------------ #
    def generate_data(self, n_train: int, n_test: int, seed: int):
        ds = make_windows(n_train + n_test, self.model.vocab_size, self.seq,
                          n_domains=self.n_domains, seed=seed)
        idx = np.arange(n_train + n_test)
        # windows are domain-interleaved, so a head/tail split keeps both
        # sides domain-balanced
        return ds.subset(idx[:n_train]), ds.subset(idx[n_train:])

    def partition_clients(self, train, n_ues, rng, malicious=None,
                          attack=None, context=""):
        return partition(train, n_ues, rng, malicious, attack,
                         group_size=self.group_size,
                         min_groups=self.min_groups,
                         max_groups=self.max_groups,
                         context=context or f"task={self.name}")

    def histogram(self, data) -> np.ndarray:
        """What a UE reports: its token histogram (claimed vocab support)."""
        return np.bincount(data.tokens.reshape(-1).astype(int),
                           minlength=self.model.vocab_size)

    def gini(self, data) -> float:
        """Eq. 2 elements diversity: Gini-Simpson over token frequencies —
        a client stuck on one domain's narrow vocabulary scores low just
        like a single-class MNIST client does."""
        return gini_simpson_hist(self.histogram(data))

    # -- eval units (host) ----------------------------------------------- #
    def unit_labels(self, test) -> np.ndarray:
        return np.asarray(test.tokens[:, 1:]).reshape(-1)

    def unit_rows(self, test) -> np.ndarray:
        return np.repeat(np.arange(len(test)), self.seq - 1)

    def eval_inputs(self, test):
        return {"tokens": jnp.asarray(test.tokens)}

    def unit_targets(self, test):
        return jnp.asarray(test.tokens[:, 1:].reshape(-1))

    # -- device plane (static under jit) ---------------------------------- #
    def init_params(self, key):
        """Seeded weights, made on the device in one program."""
        return _lm_init(self.model, key)

    def sgd_epoch(self, params, d, m, lr, batch_size: int):
        return lm_sgd_epoch_masked(self.model, params, d["tokens"], m, lr,
                                   batch_size, self.remat)

    def sgd_step(self, params, d, m, lr):
        return lm_sgd_step(self.model, params, d["tokens"], m, lr,
                           self.remat)

    def local_metric(self, params, d, m):
        return lm_correct_counts(self.model, params, d["tokens"], m)

    def predict_units(self, params, ei):
        logits, _, _, _ = lm_forward(self.model, params, ei["tokens"],
                                     window=self.model.sliding_window)
        return jnp.argmax(logits[:, :-1], -1).reshape(-1)

    def eval_loss(self, params, ei):
        """Held-out per-token cross-entropy (the LM quality metric)."""
        return _lm_eval_loss(self.model, params, ei["tokens"])

    # -- loop oracle (host) ------------------------------------------------ #
    def local_train(self, client, global_params, epochs: int, lr: float,
                    batch_size: int) -> ClientReport:
        tokens = jnp.asarray(client.data.tokens)
        params = global_params
        for _ in range(epochs):
            params = lm_sgd_epoch(self.model, params, tokens, lr,
                                  batch_size)
        m = jnp.ones(tokens.shape[0], jnp.float32)
        acc = float(count_accuracy(
            lm_correct_counts(self.model, params, tokens, m)))
        return ClientReport(ue_id=client.ue_id, params=params,
                            acc_local=acc, n_samples=client.size)

    def eval_units_loop(self, params, test, m: np.ndarray) -> float:
        if not m.any():
            return 0.0
        pred = np.asarray(_lm_predict(self.model, params,
                                      jnp.asarray(test.tokens)))
        return _f32_masked_acc(pred == self.unit_labels(test), m)

    def global_metrics(self, params, test, ei, ey, watch_class,
                       watch_target):
        """(global_acc, global_loss, source_acc, attack_success) — unit
        accuracy + held-out per-token CE; the watched pair is a (source,
        target) TOKEN pair (core.attacks.TokenFlip)."""
        pred = np.asarray(_lm_predict(self.model, params, ei["tokens"]))
        labels = self.unit_labels(test)
        ones = np.ones(labels.size, bool)
        g_acc = _f32_masked_acc(pred == labels, ones)
        g_loss = float(self.eval_loss(params, ei))
        src_acc = atk_succ = float("nan")
        if watch_class is not None:
            m = labels == watch_class
            if m.any():
                src_acc = _f32_masked_acc(pred == watch_class, m)
                if watch_target is not None:
                    atk_succ = _f32_masked_acc(pred == watch_target, m)
        return g_acc, g_loss, src_acc, atk_succ


def _f32_masked_acc(correct: np.ndarray, m: np.ndarray) -> float:
    """Masked accuracy from the same counts and the same host float32
    quotient as the vectorized engine's device evals, so the loop
    engine's Eq. 1 inputs are BIT-equal to them — a float64 ``.mean()``
    here would differ in the last mantissa bit and fork the reputation
    streams."""
    return float(count_accuracy([(correct & m).sum(), m.sum()]))


# One chip's share of Moonlight-16B-A3B fine-tuned over a FEEL cell: 1-4
# windows of 2,048 tokens per UE, one window a local step (the deployment's
# batch: under even routing each expert here would see ~192 token slots a
# step, as it would there). Plain SGD at 0.01: at 0.05 the random share's
# local loss diverges within 4 steps.
MOONLIGHT_FT = LmTask(name="moonlight_ft", model=ONE_CHIP, seq=2048,
                      n_domains=10, group_size=1, min_groups=1, max_groups=4,
                      batch_size=1, default_lr=0.01, default_n_train=50,
                      default_n_test=2, remat=True)

TASKS = {t.name: t for t in (MnistTask(), LmTask(), MOONLIGHT_FT)}


def as_task(spec) -> FeelTask:
    """Normalize a task spec: FeelTask instance (pass-through) or registry
    name. The single resolution point — server, drivers and benches all
    accept either form."""
    if isinstance(spec, FeelTask):
        return spec
    if isinstance(spec, str):
        try:
            return TASKS[spec]
        except KeyError:
            raise KeyError(f"unknown task {spec!r}; registered: "
                           f"{sorted(TASKS)}") from None
    raise TypeError(f"task spec must be a FeelTask or registry name, "
                    f"got {type(spec).__name__}")
