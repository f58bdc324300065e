"""Spark Estimator surface tests.

Mirrors the reference's integration pattern (SURVEY §4:
``test/integration/test_spark_torch.py`` / ``test_spark_keras.py`` on
local-mode Spark + temp Store): fit on synthetic data across 2 real
ranks via the local launcher, transform reproduces the trained model's
predictions, and the checkpoint lands in the Store.  Unit coverage for
Store/Params/data matches ``test_spark.py``'s store- and util-level
cases.
"""

import json
import os

import numpy as np
import pytest

from horovod_tpu.spark import LocalBackend, LocalStore
from horovod_tpu.spark.common import data as data_mod
from horovod_tpu.spark.common.params import EstimatorParams


def _regression_frame(n=256, d=4, seed=0):
    import pandas as pd

    rng = np.random.RandomState(seed)
    x = rng.rand(n, d).astype(np.float32)
    w = rng.randn(d, 1).astype(np.float32)
    y = x @ w + 0.01 * rng.randn(n, 1).astype(np.float32)
    return pd.DataFrame({"features": list(x), "label": list(y)}), x, y


def _classification_frame(n=256, d=4, k=3, seed=0):
    import pandas as pd

    rng = np.random.RandomState(seed)
    x = rng.rand(n, d).astype(np.float32)
    w = rng.randn(d, k).astype(np.float32)
    y = (x @ w).argmax(axis=1)
    return pd.DataFrame({"features": list(x), "label": y}), x, y


class TestStore:
    def test_layout_and_io(self, tmp_path):
        store = LocalStore(str(tmp_path / "store"))
        assert store.get_checkpoint_path("r1").endswith(
            os.path.join("runs", "r1", "checkpoints"))
        assert store.get_logs_path("r1").endswith(
            os.path.join("runs", "r1", "logs"))
        store.write_text("runs/r1/meta.json", json.dumps({"a": 1}))
        assert store.exists("runs/r1/meta.json")
        assert store.read_json("runs/r1/meta.json") == {"a": 1}
        assert store.get_checkpoints("r1") == []

    def test_create_factory_schemes(self, tmp_path):
        from horovod_tpu.spark import Store

        s = Store.create(f"file://{tmp_path}/s")
        assert isinstance(s, LocalStore.__mro__[1])  # FilesystemStore
        with pytest.raises(NotImplementedError, match="s3"):
            Store.create("s3://bucket/prefix")


class TestParams:
    def test_generated_accessors_roundtrip(self):
        p = EstimatorParams(epochs=5, feature_cols=["x"])
        assert p.getEpochs() == 5
        assert p.setBatchSize(64) is p  # chainable
        assert p.getBatchSize() == 64
        assert p.getFeatureCols() == ["x"]

    def test_unknown_param_rejected(self):
        with pytest.raises(ValueError, match="unknown param"):
            EstimatorParams(epohcs=5)


class TestDataMaterialization:
    def test_ragged_column_rejected(self):
        with pytest.raises(ValueError, match="ragged"):
            data_mod.to_columns(
                {"c": np.array([[1, 2], [1]], dtype=object)}, ["c"])

    def test_validation_fraction_split(self, tmp_path):
        df, x, y = _regression_frame()
        store = LocalStore(str(tmp_path))
        n_train, n_val = data_mod.materialize(
            df, store, ["features"], ["label"], validation=0.25, seed=3)
        assert n_train + n_val == len(df) and 0 < n_val < len(df)
        meta = store.read_json(store.get_data_metadata_path())
        assert meta["schema"]["features"]["shape"] == [4]

    def test_validation_indicator_column(self, tmp_path):
        import pandas as pd

        df, x, y = _regression_frame()
        df = df.assign(is_val=(np.arange(len(df)) % 4 == 0))
        store = LocalStore(str(tmp_path))
        n_train, n_val = data_mod.materialize(
            df, store, ["features"], ["label"], validation="is_val")
        assert n_val == len(df) // 4
        # the indicator column must not leak into the features
        shard = data_mod.load_shard(
            store.get_train_data_path(), data_mod.TRAIN_NPZ, 0, 1)
        assert set(shard) == {"features", "label"}

    def test_strided_shards_cover_all_rows(self, tmp_path):
        df, x, y = _regression_frame(n=101)
        store = LocalStore(str(tmp_path))
        data_mod.materialize(df, store, ["features"], ["label"])
        shards = [data_mod.load_shard(
            store.get_train_data_path(), data_mod.TRAIN_NPZ, r, 2)
            for r in range(2)]
        total = sum(len(s["label"]) for s in shards)
        assert total == 101
        merged = np.concatenate([s["features"] for s in shards])
        assert sorted(map(tuple, merged)) == sorted(map(tuple, x))


class TestTorchEstimator:
    def test_fit_transform_checkpoint_2proc(self, tmp_path):
        import torch
        import torch.nn as nn
        import torch.nn.functional as F

        from horovod_tpu.spark import TorchEstimator, TorchModel

        df, x, y = _regression_frame()
        model = nn.Sequential(nn.Linear(4, 1))
        est = TorchEstimator(
            model=model,
            optimizer=torch.optim.SGD(model.parameters(), lr=0.1),
            loss=F.mse_loss,
            feature_cols=["features"], label_cols=["label"],
            batch_size=32, epochs=3, num_proc=2, verbose=0,
            validation=0.2, random_seed=7,
            store=LocalStore(str(tmp_path)))
        tm = est.fit(df)
        assert isinstance(tm, TorchModel)
        hist = tm.getHistory()
        # training must actually train, and validation must be tracked
        assert hist["loss"][-1] < hist["loss"][0]
        assert len(hist["val_loss"]) == 3
        # checkpoint landed in the Store
        ckpt = os.path.join(
            tm.getStore().get_checkpoint_path(tm.getRunId()),
            "checkpoint.pt")
        assert os.path.exists(ckpt)
        assert tm.getStore().get_checkpoints(tm.getRunId()) \
            == ["checkpoint.pt"]
        # transform reproduces the trained module's forward pass
        out = tm.transform(df)
        trained = tm.getModel()
        with torch.no_grad():
            direct = trained(torch.from_numpy(x)).numpy().reshape(-1)
        np.testing.assert_allclose(
            out["label__output"].to_numpy().astype(np.float32),
            direct, rtol=1e-5)
        # fitting must not mutate the user's original module in place
        #  (driver reloads into a deep copy)
        assert tm.getModel() is not model
        # dict-frame input round-trips too
        dict_out = tm.transform({"features": x, "label": y})
        np.testing.assert_allclose(
            np.asarray(dict_out["label__output"], dtype=np.float32),
            direct, rtol=1e-5)

    @pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
    def test_resume_from_checkpoint_2proc(self, tmp_path):
        """Refit with the same run_id and
        resume_from_checkpoint=True continues from the Store
        checkpoint — the second fit's first-epoch loss picks up near
        the first fit's last-epoch loss, not the fresh-weights loss."""
        import torch
        import torch.nn as nn
        import torch.nn.functional as F

        from horovod_tpu.spark import TorchEstimator

        df, _x, _y = _regression_frame()

        def make_est(resume):
            model = nn.Sequential(nn.Linear(4, 1))
            torch.manual_seed(3)
            for m in model:
                if hasattr(m, "reset_parameters"):
                    m.reset_parameters()
            return TorchEstimator(
                model=model,
                optimizer=torch.optim.SGD(model.parameters(), lr=0.1),
                loss=F.mse_loss,
                feature_cols=["features"], label_cols=["label"],
                batch_size=32, epochs=2, num_proc=2, verbose=0,
                random_seed=7, run_id="resume_run",
                resume_from_checkpoint=resume,
                store=LocalStore(str(tmp_path)))

        first = make_est(resume=False).fit(df)
        h1 = first.getHistory()["loss"]
        assert h1[-1] < h1[0]
        # refit from the SAME fresh weights but resuming the run's
        # checkpoint: training continues from epoch 2's state
        second = make_est(resume=True).fit(df)
        h2 = second.getHistory()["loss"]
        # continues at (or below) roughly where the first fit ended —
        # far below the first fit's fresh-weights starting loss
        assert h2[0] < (h1[0] + h1[-1]) / 2
        assert h2[-1] <= h1[-1] * 1.5
        # a fresh fit WITHOUT resume restarts high (sanity check that
        # the assertion above is meaningful)
        fresh = make_est(resume=False).fit(df)
        assert fresh.getHistory()["loss"][0] > h2[0]

    @pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
    def test_sample_weight_col_2proc(self, tmp_path):
        """sample_weight_col (reference contract): the weight batch is
        the loss callable's third argument.  Half the rows carry a
        corrupted label with weight 0 — a weighted fit must learn the
        clean mapping anyway; an unweighted fit on the same frame must
        not (proves the weights actually flow)."""
        import pandas as pd
        import torch
        import torch.nn as nn

        from horovod_tpu.spark import TorchEstimator

        rng = np.random.RandomState(0)
        x = rng.rand(256, 4).astype(np.float32)
        w_true = rng.randn(4, 1).astype(np.float32)
        y = x @ w_true
        weight = np.ones(256, np.float32)
        weight[::2] = 0.0
        y_corrupt = y.copy()
        y_corrupt[::2] += 25.0  # zero-weighted rows are poisoned
        df = pd.DataFrame({"features": list(x),
                           "label": list(y_corrupt),
                           "w": weight})

        def weighted_mse(output, label, weight):
            return ((output - label) ** 2 * weight.unsqueeze(1)).sum() \
                / weight.sum().clamp(min=1.0)

        def make_est(loss, sw_col, run_id):
            model = nn.Sequential(nn.Linear(4, 1))
            torch.manual_seed(3)
            for m in model:
                if hasattr(m, "reset_parameters"):
                    m.reset_parameters()
            return TorchEstimator(
                model=model,
                optimizer=torch.optim.SGD(model.parameters(), lr=0.2),
                loss=loss, feature_cols=["features"],
                label_cols=["label"], sample_weight_col=sw_col,
                batch_size=32, epochs=4, num_proc=2, verbose=0,
                random_seed=7, run_id=run_id,
                store=LocalStore(str(tmp_path)))

        tm = make_est(weighted_mse, "w", "weighted").fit(df)
        pred = np.asarray(
            tm.transform({"features": x, "label": y})["label__output"],
            dtype=np.float32)
        clean_mse = float(((pred - y) ** 2).mean())
        assert clean_mse < 0.5  # learned the CLEAN mapping

        um = make_est(torch.nn.functional.mse_loss, None,
                      "unweighted").fit(df)
        upred = np.asarray(
            um.transform({"features": x, "label": y})["label__output"],
            dtype=np.float32)
        # pulled toward the +25 poisoned rows: far from clean labels
        assert float(((upred - y) ** 2).mean()) > clean_mse * 10

    @pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
    def test_loss_weights_and_gradient_compression_params(
            self, tmp_path):
        """Reference param spellings: loss_weights scales each
        output's loss (exactly 2x on the first step), and
        gradient_compression is accepted alongside compression."""
        import torch
        import torch.nn as nn
        import torch.nn.functional as F

        from horovod_tpu.spark import TorchEstimator

        df, _x, _y = _regression_frame()

        def make_est(run_id, **kw):
            model = nn.Sequential(nn.Linear(4, 1))
            torch.manual_seed(3)
            for m in model:
                if hasattr(m, "reset_parameters"):
                    m.reset_parameters()
            return TorchEstimator(
                model=model,
                optimizer=torch.optim.SGD(model.parameters(), lr=0.1),
                loss=F.mse_loss, feature_cols=["features"],
                label_cols=["label"], batch_size=32, epochs=1,
                train_steps_per_epoch=1, num_proc=2, verbose=0,
                random_seed=7, run_id=run_id,
                store=LocalStore(str(tmp_path)), **kw)

        base = make_est("lw_base").fit(df).getHistory()["loss"][0]
        doubled = make_est(
            "lw_x2", loss_weights=[2.0]).fit(df).getHistory()["loss"][0]
        assert abs(doubled - 2.0 * base) < 1e-4 * max(abs(base), 1.0)

        # reference spelling of the compression knob
        est = make_est("gc_fp16", gradient_compression="fp16")
        assert est.getGradientCompression() == "fp16"
        h = est.fit(df).getHistory()["loss"]
        assert len(h) == 1

        # mismatched loss_weights length fails loudly
        with pytest.raises(Exception, match="loss_weights"):
            make_est("lw_bad", loss_weights=[1.0, 2.0]).fit(df)

    def test_sample_weight_col_driver_side_guards(self, tmp_path):
        import torch
        import torch.nn as nn

        from horovod_tpu.spark import TorchEstimator

        df, _x, _y = _regression_frame()
        df["w"] = 1.0
        model = nn.Sequential(nn.Linear(4, 1))

        def est(**kw):
            return TorchEstimator(
                model=model,
                optimizer=torch.optim.SGD(model.parameters(), lr=0.1),
                feature_cols=["features"], label_cols=["label"],
                batch_size=32, epochs=1, num_proc=2,
                store=LocalStore(str(tmp_path)), **kw)

        # 2-arg module loss fails at fit() on the DRIVER, not with a
        # TypeError deep inside a worker rank
        with pytest.raises(ValueError, match="sample_weight"):
            est(loss=nn.MSELoss(), sample_weight_col="w").fit(df)
        # weights + transformation_fn would silently misalign rows
        with pytest.raises(ValueError, match="transformation_fn"):
            est(loss=lambda o, y, w: ((o - y) ** 2 * w).mean(),
                sample_weight_col="w",
                transformation_fn=lambda f, l: (f, l)).fit(df)

    def test_sample_weight_nonweight_third_arg_warns(self, tmp_path):
        """ADVICE r5: a REQUIRED third positional that doesn't look
        like a weight (focal's `gamma`) passes the arity gate but gets
        a warning naming the parameter — the weight batch is about to
        bind to a hyperparameter and train silently wrong."""
        import warnings

        import torch
        import torch.nn as nn

        from horovod_tpu.spark import TorchEstimator

        model = nn.Sequential(nn.Linear(4, 1))

        def est(loss):
            return TorchEstimator(
                model=model,
                optimizer=torch.optim.SGD(model.parameters(), lr=0.1),
                feature_cols=["features"], label_cols=["label"],
                batch_size=32, epochs=1, num_proc=2,
                store=LocalStore(str(tmp_path)),
                loss=loss, sample_weight_col="w")

        def focal(output, target, gamma):
            return ((output - target) ** 2 * gamma).mean()

        with pytest.warns(UserWarning, match="'gamma'"):
            est(focal)._check_params()

        # a weight-named third arg stays silent
        def weighted(output, target, sample_weight):
            return ((output - target) ** 2 * sample_weight).mean()

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            est(weighted)._check_params()

    def test_sample_weight_strict_mode_errors(self, tmp_path,
                                              monkeypatch):
        """HVTPU_SPARK_STRICT upgrades the non-weight-third-arg warning
        to a hard error at fit() time, still naming the parameter —
        for pipelines that would rather fail than risk a silently
        misweighted model."""
        import torch
        import torch.nn as nn

        from horovod_tpu.spark import TorchEstimator

        model = nn.Sequential(nn.Linear(4, 1))

        def focal(output, target, gamma):
            return ((output - target) ** 2 * gamma).mean()

        est = TorchEstimator(
            model=model,
            optimizer=torch.optim.SGD(model.parameters(), lr=0.1),
            feature_cols=["features"], label_cols=["label"],
            batch_size=32, epochs=1, num_proc=2,
            store=LocalStore(str(tmp_path)),
            loss=focal, sample_weight_col="w")

        monkeypatch.setenv("HVTPU_SPARK_STRICT", "1")
        with pytest.raises(ValueError, match="'gamma'"):
            est._check_params()
        # falsy spellings keep the warning behavior
        monkeypatch.setenv("HVTPU_SPARK_STRICT", "0")
        with pytest.warns(UserWarning, match="HVTPU_SPARK_STRICT"):
            est._check_params()

    def test_lightning_shim_raises_with_guidance(self):
        from horovod_tpu.spark.lightning import LightningEstimator

        with pytest.raises(ImportError, match="TorchEstimator"):
            LightningEstimator(model=object(), num_proc=2)

    def test_lightning_shim_upstream_name(self):
        # the reference exports the lightning estimator as
        # horovod.spark.lightning.TorchEstimator — same path here
        import horovod_tpu.spark.lightning as l

        assert l.TorchEstimator is l.LightningEstimator
        with pytest.raises(ImportError, match="migration.md"):
            l.TorchEstimator(model=object())

    def test_shard_smaller_than_batch_still_trains(self, tmp_path):
        """The tail batch must train (drop_last=False): 50 rows over 2
        ranks at batch_size=32 means every rank's shard (25 rows) is
        smaller than one batch."""
        import torch
        import torch.nn as nn
        import torch.nn.functional as F

        from horovod_tpu.spark import TorchEstimator

        df, x, y = _regression_frame(n=50)
        model = nn.Sequential(nn.Linear(4, 1))
        est = TorchEstimator(
            model=model,
            optimizer=torch.optim.SGD(model.parameters(), lr=0.1),
            loss=F.mse_loss,
            feature_cols=["features"], label_cols=["label"],
            batch_size=32, epochs=4, num_proc=2, verbose=0,
            random_seed=7, store=LocalStore(str(tmp_path)))
        tm = est.fit(df)
        hist = tm.getHistory()
        assert hist["loss"][-1] < hist["loss"][0]
        assert all(v > 0 for v in hist["loss"])  # steps actually ran

    def test_compression_param_object_and_typo(self):
        """compression accepts the reference's object style and names a
        clear error for typos (shared resolve_compression)."""
        import horovod_tpu.torch as hvd_torch

        from horovod_tpu.spark.common.estimator import \
            resolve_compression

        assert resolve_compression(hvd_torch, None) \
            is hvd_torch.Compression.none
        assert resolve_compression(hvd_torch, "fp16") \
            is hvd_torch.Compression.fp16
        assert resolve_compression(
            hvd_torch, hvd_torch.Compression.fp16) \
            is hvd_torch.Compression.fp16
        with pytest.raises(ValueError, match="options"):
            resolve_compression(hvd_torch, "fp32")

    @pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
    def test_keras_uneven_shards_train_in_lockstep(self, tmp_path):
        """65 rows over 2 ranks: without the min-rows trim, rank 0
        runs one more gradient-allreduce batch than rank 1 and the
        epoch deadlocks."""
        import keras

        from horovod_tpu.spark import KerasEstimator

        df, x, y = _classification_frame(n=65)
        model = keras.Sequential([
            keras.layers.Input((4,)),
            keras.layers.Dense(3, activation="softmax"),
        ])
        est = KerasEstimator(
            model=model, optimizer=keras.optimizers.SGD(0.2),
            loss="sparse_categorical_crossentropy",
            feature_cols=["features"], label_cols=["label"],
            batch_size=32, epochs=2, num_proc=2, verbose=0,
            random_seed=7, store=LocalStore(str(tmp_path)))
        km = est.fit(df)
        assert len(km.getHistory()["loss"]) == 2

    def test_steps_cap_below_bps_raises(self, tmp_path):
        import torch
        import torch.nn as nn
        import torch.nn.functional as F

        from horovod_tpu.runner import RunError
        from horovod_tpu.spark import TorchEstimator

        df, x, y = _regression_frame(n=128)
        model = nn.Sequential(nn.Linear(4, 1))
        est = TorchEstimator(
            model=model,
            optimizer=torch.optim.SGD(model.parameters(), lr=0.1),
            loss=F.mse_loss,
            feature_cols=["features"], label_cols=["label"],
            batch_size=16, epochs=1, num_proc=2, verbose=0,
            train_steps_per_epoch=1, backward_passes_per_step=2,
            store=LocalStore(str(tmp_path)))
        with pytest.raises(RunError, match="no optimizer step"):
            est.fit(df)

    def test_uneven_shards_bps_and_compression(self, tmp_path):
        """127 rows over 2 ranks (64/63-row shards would flip the
        per-rank batch count and deadlock without the rank-consistent
        step derivation) + backward_passes_per_step=2 local
        aggregation + fp16 wire compression, all through the estimator
        params (reference TorchEstimator knobs)."""
        import torch
        import torch.nn as nn
        import torch.nn.functional as F

        from horovod_tpu.spark import TorchEstimator

        df, x, y = _regression_frame(n=127)
        model = nn.Sequential(nn.Linear(4, 1))
        est = TorchEstimator(
            model=model,
            optimizer=torch.optim.SGD(model.parameters(), lr=0.1),
            loss=F.mse_loss,
            feature_cols=["features"], label_cols=["label"],
            batch_size=16, epochs=4, num_proc=2, verbose=0,
            backward_passes_per_step=2, compression="fp16",
            random_seed=7, store=LocalStore(str(tmp_path)))
        tm = est.fit(df)
        hist = tm.getHistory()
        assert len(hist["loss"]) == 4
        assert hist["loss"][-1] < hist["loss"][0]

    def test_missing_params_raise(self, tmp_path):
        import torch.nn as nn

        from horovod_tpu.spark import TorchEstimator

        est = TorchEstimator(model=nn.Linear(2, 1),
                             feature_cols=["f"], label_cols=["l"],
                             store=LocalStore(str(tmp_path)))
        with pytest.raises(ValueError, match="optimizer"):
            est.fit({"f": np.zeros((4, 2)), "l": np.zeros(4)})


class TestKerasEstimator:
    @pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
    def test_fit_transform_checkpoint_2proc(self, tmp_path):
        import keras

        from horovod_tpu.spark import KerasEstimator, KerasModel

        df, x, y = _classification_frame()
        model = keras.Sequential([
            keras.layers.Input((4,)),
            keras.layers.Dense(8, activation="relu"),
            keras.layers.Dense(3, activation="softmax"),
        ])
        est = KerasEstimator(
            model=model, optimizer=keras.optimizers.SGD(0.2),
            loss="sparse_categorical_crossentropy",
            metrics=["accuracy"],
            feature_cols=["features"], label_cols=["label"],
            batch_size=32, epochs=3, num_proc=2, verbose=0,
            validation=0.2, random_seed=7,
            store=LocalStore(str(tmp_path)))
        km = est.fit(df)
        assert isinstance(km, KerasModel)
        hist = km.getHistory()
        assert hist["loss"][-1] < hist["loss"][0]
        assert set(hist) >= {"loss", "accuracy", "val_loss",
                             "val_accuracy"}
        ckpt_dir = km.getStore().get_checkpoint_path(km.getRunId())
        assert os.path.exists(os.path.join(ckpt_dir, "checkpoint.npz"))
        assert os.path.exists(os.path.join(ckpt_dir, "model.json"))
        out = km.transform(df)
        pred = np.stack(out["label__output"].to_numpy())
        assert pred.shape == (len(df), 3)
        # transform == the trained model's own predict
        direct = km.getModel().predict(x, verbose=0)
        np.testing.assert_allclose(pred, direct, rtol=1e-5)
        assert (pred.argmax(1) == y).mean() > 0.7


    @pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
    def test_keras_resume_from_checkpoint_2proc(self, tmp_path):
        """Keras analog of the torch resume test: refit with the same
        run_id and resume_from_checkpoint=True loads the Store
        checkpoint over the shipped weights."""
        import keras

        from horovod_tpu.spark import KerasEstimator

        df, _x, _y = _classification_frame()

        def make_est(resume):
            keras.utils.set_random_seed(3)
            model = keras.Sequential([
                keras.layers.Input((4,)),
                keras.layers.Dense(8, activation="relu"),
                keras.layers.Dense(3, activation="softmax"),
            ])
            return KerasEstimator(
                model=model, optimizer=keras.optimizers.SGD(0.2),
                loss="sparse_categorical_crossentropy",
                feature_cols=["features"], label_cols=["label"],
                batch_size=32, epochs=2, num_proc=2, verbose=0,
                random_seed=7, run_id="keras_resume_run",
                resume_from_checkpoint=resume,
                store=LocalStore(str(tmp_path)))

        h1 = make_est(resume=False).fit(df).getHistory()["loss"]
        assert h1[-1] < h1[0]
        h2 = make_est(resume=True).fit(df).getHistory()["loss"]
        # resumes near the first fit's end, far below its start
        assert h2[0] < (h1[0] + h1[-1]) / 2
        # a fresh fit restarts high (the assertion above is meaningful)
        fresh = make_est(resume=False).fit(df).getHistory()["loss"]
        assert fresh[0] > h2[0]


class TestBackends:
    def test_local_backend_runs_across_ranks(self):
        backend = LocalBackend(num_proc=2)
        assert backend.num_processes() == 2

    def test_spark_backend_without_pyspark_defaults(self):
        from horovod_tpu.spark import SparkBackend

        b = SparkBackend()
        assert b.num_processes() == 2  # no pyspark here: default
