"""Plain reference of one served trial: sync FedAvg rounds over a
homogeneous fleet, the FedTune controller and the paper's cost accounting.

It imports nothing of the program. The federation is made from the trial's
seed by a copy of the generator's arithmetic (``Federation``), so it sees
the same clients; everything after that is written here, one client at a
time: SGD with momentum on the masked mean cross-entropy, the
example-weighted mean of the clients' models, accuracy on the pooled test
set, eqs. (2)-(5) of the paper charged per round, and Algorithm 1 of the
paper deciding (M, E). The model itself (initial weights, forward pass,
parameter and FLOP counts) is the configuration's kind, ``models/<kind>.py``
found through ``kinds.py``, which imports nothing of the program either.

``dtype=float32`` keeps the model in float32 with matrix products at the
precision the configuration states (``precision.matmul``: ``default`` is
the backend's default, one bfloat16 pass with float32 accumulation on a
TPU, as the served path runs; ``highest`` is six passes) and the cost
accounting in float64. ``dtype=bfloat16`` is the control: the same
reference one step below, bfloat16 for the model and float32 for the
accounting.

``served`` holds the served run's global model after each round. Round
``r`` then starts from the served model after round ``r - 1`` (the first
from the reference's own initial model), so each round is compared on its
own, without the rounding of the rounds before it; the reference also
reads the accuracy of each served model. ``forced_acc`` feeds the
controller and the target check with the accuracies a served run
reported, round by round, so that one accuracy read a point apart cannot
send the two runs down different (M, E) trajectories.
"""

from __future__ import annotations

import json
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

import kinds

EPS = 1e-12
M_SIGNS = (1.0, 1.0, -1.0, -1.0)   # CompT, TransT, CompL, TransL
E_SIGNS = (-1.0, 1.0, -1.0, 1.0)
TEST_KEY0 = 10_000_000


class Federation:
    """The synthetic federation of ``data/synthetic.py``, made from its
    seed with the same draws in the same order."""

    def __init__(self, data: dict, seed: int):
        self.d = data
        self.seed = seed
        self.feat = int(np.prod(data["shape"]))
        rng = np.random.default_rng(seed)
        means = rng.normal(0.0, 1.0, size=(data["n_classes"], self.feat))
        means /= np.linalg.norm(means, axis=1, keepdims=True)
        means *= np.sqrt(self.feat) / 8.0
        self.sizes = np.clip(
            rng.lognormal(data["size_log_mean"], data["size_log_std"],
                          size=data["n_train_clients"]),
            data["size_min"], data["size_max"]).astype(np.int64)
        self.means = means.astype(np.float32)

    def client(self, key: int, n: int):
        d = self.d
        rng = np.random.default_rng((self.seed * 1_000_003 + key) % (2 ** 63))
        label_p = rng.dirichlet(np.full(d["n_classes"], d["dirichlet_alpha"]))
        y = rng.choice(d["n_classes"], size=n, p=label_p)
        shift = rng.normal(0.0, d["client_shift"], size=(self.feat,))
        x = (d["separation"] * self.means[y] + shift[None, :]
             + rng.normal(0.0, d["noise"], size=(n, self.feat)))
        if d["label_noise"] > 0:
            flip = rng.random(n) < d["label_noise"]
            y = np.where(flip, rng.integers(0, d["n_classes"], n), y)
        return x.astype(np.float32), y.astype(np.int32)

    def test_set(self, points: int):
        d = self.d
        rng = np.random.default_rng(self.seed + 777)
        xs, ys, total = [], [], 0
        for tc in range(d["n_test_clients"]):
            n = int(np.clip(rng.lognormal(d["size_log_mean"],
                                          d["size_log_std"]),
                            d["size_min"], d["size_max"]))
            x, y = self.client(TEST_KEY0 + tc, n)
            xs.append(x)
            ys.append(y)
            total += n
            if total >= points:
                break
        return np.concatenate(xs)[:points], np.concatenate(ys)[:points]


def batches(x, y, batch_size: int, passes: float, rng):
    """``passes`` epochs of one client's data in shuffled batches, the last
    one padded and masked."""
    n = len(y)
    total = int(round(passes * n))
    if total <= 0:
        return []
    order = np.concatenate([rng.permutation(n)
                            for _ in range(int(np.ceil(total / n)))])[:total]
    out = []
    for start in range(0, total, batch_size):
        idx = order[start:start + batch_size]
        bx = np.zeros((batch_size, x.shape[1]), np.float32)
        by = np.zeros(batch_size, np.int32)
        bm = np.zeros(batch_size, np.bool_)
        bx[:len(idx)], by[:len(idx)], bm[:len(idx)] = x[idx], y[idx], True
        out.append((bx, by, bm))
    return out


def _precision(dtype, matmul: str):
    return (jax.lax.Precision.HIGHEST
            if dtype == jnp.float32 and matmul == "highest"
            else jax.lax.Precision.DEFAULT)


@lru_cache(maxsize=None)
def _local_sgd(model_key: str, dtype_name: str, matmul: str, lr: float,
               momentum: float):
    """One client's local training: SGD with momentum over its batches in
    order. Batches past the client's last are padding (``live`` false) and
    leave the state as it was, so step counts can be rounded up to a power
    of two and the compiled shapes stay few. ``model_key`` is the
    configuration's ``model`` entry (its kind and sizes) as JSON."""
    model = json.loads(model_key)
    logits = kinds.of(model).logits
    dtype = jnp.dtype(dtype_name)
    prec = _precision(dtype, matmul)

    def loss(params, x, y, mask):
        logp = jax.nn.log_softmax(logits(params, x, prec, model), axis=-1)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return (jnp.where(mask, nll, 0).sum()
                / jnp.maximum(mask.sum(), 1).astype(dtype))

    def step(state, batch):
        params, mu = state
        x, y, mask, live = batch
        g = jax.grad(loss)(params, x.astype(dtype), y, mask)
        new_mu = [momentum * m + gi for m, gi in zip(mu, g)]
        new_p = [p - lr * m for p, m in zip(params, new_mu)]
        keep = lambda new, old: [jnp.where(live, a, b)  # noqa: E731
                                 for a, b in zip(new, old)]
        return (keep(new_p, params), keep(new_mu, mu)), None

    @jax.jit
    def train(params, xs, ys, masks, live):
        mu = [jnp.zeros_like(p) for p in params]
        (params, _), _ = jax.lax.scan(step, (params, mu),
                                      (xs, ys, masks, live))
        return params

    return train


@lru_cache(maxsize=None)
def _correct(model_key: str, dtype_name: str, matmul: str):
    model = json.loads(model_key)
    logits = kinds.of(model).logits
    dtype = jnp.dtype(dtype_name)
    prec = _precision(dtype, matmul)

    @jax.jit
    def count(params, x, y):
        out = logits(params, x.astype(dtype), prec, model)
        return jnp.sum(jnp.argmax(out, -1) == y)

    return count


class FedTune:
    """Algorithm 1 of the paper: a decision whenever accuracy has gained at
    least eps since the last one; (M, E) step by one along the signs of
    eqs. (10) and (11), slopes penalised by D after a bad move (eq. 6)."""

    def __init__(self, pref, m0: int, e0: float, cfg: dict, ftype):
        self.w = [ftype(v) for v in pref]
        self.cfg, self.f = cfg, ftype
        self.m, self.e = m0, e0
        self.prev = None                 # (m, e) at the last decision
        self.last_acc = ftype(0.0)
        self.window = [ftype(0.0)] * 4
        self.prv = self.prvprv = None
        self.eta = [ftype(1.0)] * 4
        self.zeta = [ftype(1.0)] * 4

    def on_round(self, acc: float, round_cost, m: int, e: float):
        f = self.f
        self.m, self.e = m, e
        self.window = [a + c for a, c in zip(self.window, round_cost)]
        gain = f(acc) - self.last_acc
        if gain < self.cfg["eps"]:
            return m, e
        cur = [v / gain for v in self.window]
        if self.prv is not None:
            bad = sum(self.w[i] * (cur[i] - self.prv[i])
                      / max(self.prv[i], f(EPS))
                      for i in range(4) if self.w[i] != 0.0) > 0.0
            self._slopes(cur, bad)
            dm = self._delta(cur, self.eta, M_SIGNS)
            de = self._delta(cur, self.zeta, E_SIGNS)
            nm = m + (0 if dm == 0.0 else (1 if dm > 0 else -1))
            ne = e + (0 if de == 0.0 else (1 if de > 0 else -1))
        else:
            nm, ne = m + 1, e
        nm, ne = _clamp(nm, ne, self.cfg["m_max"], self.cfg["e_max"])
        self.prev = (m, e)
        self.prvprv, self.prv = self.prv, cur
        self.last_acc = f(acc)
        self.window = [f(0.0)] * 4
        return nm, ne

    def _slopes(self, cur, bad: bool):
        def slope(i):
            if self.prvprv is None:
                return self.f(1.0)
            num = abs(cur[i] - self.prv[i])
            return num / max(abs(self.prv[i] - self.prvprv[i]), self.f(EPS))

        for now, last, s, up_f, down_f in (
                (self.m, self.prev[0], self.eta, (0, 1), (2, 3)),
                (self.e, self.prev[1], self.zeta, (1, 3), (0, 2))):
            if now == last:
                continue
            up = now > last
            favored, opposing = (up_f, down_f) if up else (down_f, up_f)
            for i in favored:
                s[i] = slope(i)
            if bad:
                for i in opposing:
                    s[i] = s[i] * self.cfg["penalty"]

    def _delta(self, cur, slopes, signs):
        total = self.f(0.0)
        for i in range(4):
            if self.w[i] == 0.0:
                continue
            diff = abs(cur[i] - self.prv[i])
            total += signs[i] * self.w[i] * slopes[i] * diff / max(
                cur[i], self.f(EPS))
        return total


def _clamp(m, e, m_max, e_max):
    return int(min(max(m, 1), m_max)), float(min(max(e, 1.0), e_max))


class _Plain:
    """What every mode shares: the federation, the model's steps, the cost
    constants and the controller, made from one trial's spec."""

    def __init__(self, spec: dict, config: dict, dtype, forced_acc):
        self.spec, self.config, self.dtype = spec, config, dtype
        self.forced = forced_acc
        dname = jnp.dtype(dtype).name
        self.f = float if dtype == jnp.float32 else np.float32
        tr, model = config["train"], config["model"]
        self.fed = Federation(config["data"], spec["seed"])
        self.k = config["data"]["n_train_clients"]
        xt, self.yt = self.fed.test_set(spec["eval_points"])
        self.xt = jnp.asarray(xt)
        matmul = config["precision"]["matmul"]
        kind, mkey = kinds.of(model), json.dumps(model, sort_keys=True)
        self.local = _local_sgd(mkey, dname, matmul, tr["lr"],
                                tr["momentum"])
        self.count = _correct(mkey, dname, matmul)
        self.params = [p.astype(dtype)
                       for p in kind.init_params(model, spec["seed"])]
        self.rng = np.random.default_rng(spec["seed"])
        n_params = kind.param_count(model)
        fwd = kind.forward_flops(model)
        self.c1 = self.f(fwd) * self.f(config["costs"]["backward_multiplier"])
        self.down = self.up = self.f(n_params) * self.f(0.5)
        self.total = [self.f(0.0)] * 4
        self.tuner = FedTune(spec["preference"], spec["m0"], spec["e0"],
                             config["fedtune"], self.f)
        self.m, self.e = spec["m0"], float(spec["e0"])
        self.hist_m, self.hist_e, self.hist_acc = [], [], []
        self.reached = False
        self.models = []

    def train(self, params, cid: int, e: float):
        x, y = self.fed.client(cid, int(self.fed.sizes[cid]))
        steps = batches(x, y, self.spec["batch_size"], e, self.rng)
        if not steps:
            return params
        t_pad = 1 << (len(steps) - 1).bit_length()
        xs = np.zeros((t_pad,) + steps[0][0].shape, np.float32)
        ys = np.zeros((t_pad,) + steps[0][1].shape, np.int32)
        ms = np.zeros((t_pad,) + steps[0][2].shape, np.bool_)
        for i, (bx, by, bm) in enumerate(steps):
            xs[i], ys[i], ms[i] = bx, by, bm
        return self.local(params, xs, ys, ms, np.arange(t_pad) < len(steps))

    def charge(self, round_cost):
        self.total = [a + b for a, b in zip(self.total, round_cost)]

    def finish_round(self, round_cost) -> bool:
        """Evaluate, record, check the target, step the controller; True
        once the trial has reached its target."""
        self.charge(round_cost)
        r = len(self.hist_m)
        self.models.append(self.params)
        acc = self.accuracy(self.params)
        use = (self.forced[r] if self.forced is not None
               and r < len(self.forced) else acc)
        self.hist_m.append(self.m)
        self.hist_e.append(self.e)
        self.hist_acc.append(acc)
        if use >= self.spec["target_accuracy"]:
            self.reached = True
            return True
        m, e = self.tuner.on_round(use, round_cost, self.m, self.e)
        self.m, self.e = _clamp(m, e, self.k, 100.0)
        return False

    def accuracy(self, params) -> float:
        return int(self.count(params, self.xt, jnp.asarray(self.yt))) \
            / len(self.yt)

    def result(self, served=None) -> dict:
        out = {
            "history_m": self.hist_m, "history_e": self.hist_e,
            "history_acc": self.hist_acc,
            "cost": [float(c) for c in self.total],
            "rounds": len(self.hist_m), "reached": self.reached,
            "final_m": self.m, "final_e": self.e,
            "models": [[np.asarray(p, np.float32) for p in m]
                       for m in self.models]}
        if served is not None:
            out["served_acc"] = [self.accuracy(self._cast(m))
                                 for m in served]
        return out

    def _cast(self, leaves) -> list:
        return [jnp.asarray(p, self.dtype) for p in leaves]


def run_trial(spec: dict, config: dict, *, dtype=jnp.float32,
              forced_acc=None, served=None) -> dict:
    """One trial from its spec: its round record and its global model after
    every round."""
    if (spec["aggregator"], spec["tuner"], spec["mode"], spec["het"]) != (
            "fedavg", "fedtune", "sync", "homogeneous"):
        raise ValueError("the reference covers sync FedAvg with FedTune on "
                         "a homogeneous fleet")
    t = _Plain(spec, config, dtype, forced_acc)
    f = t.f
    for r in range(spec["rounds"]):
        if served is not None and 0 < r <= len(served):
            t.params = t._cast(served[r - 1])
        cids = [int(c) for c in t.rng.choice(t.k, size=min(t.m, t.k),
                                              replace=False)]
        sizes = [int(t.fed.sizes[c]) for c in cids]
        trained = [t.train(t.params, c, t.e) for c in cids]
        n_tot = float(sum(sizes))
        w = [jnp.asarray(n / n_tot, t.dtype) for n in sizes]
        t.params = [sum(wi * p[j] for wi, p in zip(w, trained))
                    for j in range(len(t.params))]
        comp = [t.c1 * t.e * n for n in sizes]
        round_cost = (max(comp), t.down + t.up, t.c1 * t.e * f(n_tot),
                      t.down * len(cids) + t.up * len(cids))
        if t.finish_round(round_cost):
            break
    return t.result(served)
