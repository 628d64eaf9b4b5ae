"""LSTM sentiment classifier on IMDB.

Reference: ``theanompi/models/lasagne_model_zoo/lstm.py`` — the Lasagne
LSTM on IMDB sentiment, the reference's GoSGD demo and its only
recurrent model (named in BASELINE.json's model list).

TPU-native rebuild: Embedding → masked LSTM (``lax.scan``) → masked
mean-pool → Dropout → FC(2), per the classic Theano IMDB recipe.  Runs
under all three rules; tokens stay int32 through ``prep_input`` (the
generic classifier pipeline casts inputs to bf16, which would corrupt
ids above 256).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from theanompi_tpu.models.base import ClassifierModel
from theanompi_tpu.models.data.imdb import ImdbData, N_CLASSES, PAD_ID
from theanompi_tpu.obs.setup import setup_phase
from theanompi_tpu.ops.layers import FC, Dropout, Layer
from theanompi_tpu.ops.recurrent import LSTM as LSTMLayer
from theanompi_tpu.ops.recurrent import Embedding


class _ImdbNet(Layer):
    """Embedding→LSTM→pool→dropout→FC with the pad mask threaded
    through (Sequential can't pass masks between layers)."""

    def __init__(self, vocab, emb_dim, hidden, dropout, compute_dtype):
        self.embed = Embedding(vocab, emb_dim, out_dtype=compute_dtype)
        self.lstm = LSTMLayer(hidden, pool="mean")
        self.drop = Dropout(dropout)
        self.fc = FC(N_CLASSES)

    def init(self, key, in_shape):
        k1, k2, k3 = jax.random.split(key, 3)
        p_e, _, sh = self.embed.init(k1, in_shape)
        p_l, _, sh = self.lstm.init(k2, sh)
        p_f, _, sh = self.fc.init(k3, sh)
        return {"embed": p_e, "lstm": p_l, "fc": p_f}, {}, sh

    def apply(self, params, state, x, *, train=False, rng=None):
        # x is int32 by the model's prep_input contract; Embedding
        # keeps its own defensive cast for direct use.
        mask = (x != PAD_ID)
        h, _ = self.embed.apply(params["embed"], {}, x)
        h, _ = self.lstm.apply(params["lstm"], {}, h, mask=mask)
        h, _ = self.drop.apply({}, {}, h, train=train, rng=rng)
        logits, _ = self.fc.apply(params["fc"], {}, h)
        return logits, state


class LSTM(ClassifierModel):
    """IMDB sentiment LSTM under the model contract."""

    def __init__(self, config: dict | None = None):
        config = dict(config or {})
        config.setdefault("lr", 0.1)
        config.setdefault("weight_decay", 0.0)
        config.setdefault("n_epochs", 15)
        config.setdefault("batch_size", 32)
        super().__init__(config)
        self.vocab = int(config.get("vocab", 10000))
        self.emb_dim = int(config.get("emb_dim", 128))
        self.hidden = int(config.get("hidden", 128))
        self.dropout = float(config.get("dropout", 0.5))
        self.maxlen = int(config.get("maxlen", 100))

    def prep_input(self, x):
        return x.astype(jnp.int32)   # token ids must not be cast to bf16

    def build_model(self, n_replicas: int = 1) -> None:
        self.net = _ImdbNet(
            self.vocab, self.emb_dim, self.hidden, self.dropout,
            self.compute_dtype,
        )
        self.input_shape = (self.maxlen,)
        with setup_phase("data"):
            self.data = ImdbData(
                batch_size=self.config.get("batch_size", 32),
                n_replicas=n_replicas,
                maxlen=self.maxlen,
                vocab=self.vocab,
                seed=self.seed,
                n_train=self.config.get("n_train"),
                n_val=self.config.get("n_val"),
            )
        self._init_params()
