"""deepfm_criteo: the system under test, and the counts its metrics need.

`build` goes through `models.deepfm.build_train_program` and `Executor`. Two
things lean on the program beyond that, and both are its storage format, not
its arithmetic: `pack_rows` / `unpack_rows` of ops/deferred_rows.py turn the
benchmark's float32 rows into the packed u16 rows of the table and back (a
later PR that changes the row format takes these with it), and `hbm` goes
through benchmark/program_access.py. The plain reference is beside this file
and imports none of this."""
from __future__ import annotations

from benchmark import program_access


def counts(cfg: dict, traffic: dict) -> dict:
    return {"examples_per_step": traffic["batch"],
            "rows_per_step": traffic["batch"] * cfg["num_fields"]}


def work_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"]


class System:
    """One compiled training step with its state: built once in set-up,
    checked on its first steps and handed as it is to the window."""

    TABLE = "fm_t"

    def __init__(self, cfg: dict, traffic: dict, chips: int):
        import paddle_tpu as fluid
        from paddle_tpu.models import deepfm

        if chips != 1 or traffic.get("layout", "single") != "single":
            raise ValueError("deepfm_criteo runs on one chip")
        self.cfg = cfg
        self._fluid = fluid
        topt, dopt = cfg["table_optimizer"], cfg["dense_optimizer"]
        if topt["learning_rate"] != dopt["learning_rate"]:
            raise ValueError("the model file gives both rules one rate")
        rows_per_step = traffic["batch"] * cfg["num_fields"]
        with fluid.unique_name.guard():     # the same names every build
            self.main, self.startup, _, self.loss, _ = (
                deepfm.build_train_program(
                    vocab_size=cfg["table_rows"], num_fields=cfg["num_fields"],
                    num_dense=cfg["num_dense"], embed_dim=cfg["embedding_dim"],
                    lr=dopt["learning_rate"], is_sparse=True, fused_table=True,
                    embedding_optimizer=topt["name"],
                    packed_rows={"rows_per_step": rows_per_step},
                    hidden_sizes=tuple(cfg["hidden_sizes"])))
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.scope = fluid.Scope()
        self.vis = cfg["embedding_dim"] + 1
        self._last_feed = None

    def start(self, weights: dict) -> None:
        """Startup (the whole table from the program's generator, optimizer
        state), then the benchmark's seeded dense weights and, over the rows
        the checked steps touch, its seeded rows and fresh accumulators."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.deferred_rows import pack_rows

        with self._fluid.scope_guard(self.scope):
            self.exe.run(self.startup)
        for name, value in weights["dense"].items():
            if not self.scope.has_var(name):
                raise KeyError(f"the program has no parameter {name!r}")
            self.scope.set_var(name, value)
        acc0 = self.cfg["table_optimizer"]["initial_accumulator_value"]

        def write(table, ids, rows):
            full = jnp.concatenate(
                [rows, jnp.full(rows.shape, acc0, jnp.float32)], axis=-1)
            # ids past the table's end pad `row_ids` to a fixed length
            return table.at[ids].set(pack_rows(full), mode="drop",
                                     unique_indices=True)

        self._row_ids = jnp.asarray(weights["row_ids"])
        table = jax.jit(write, donate_argnums=(0,))(
            self.scope.find_var(self.TABLE), self._row_ids, weights["rows"])
        self.scope.set_var(self.TABLE, table)
        self._dense = list(weights["dense"])

    def step(self, batch: dict):
        self._last_feed = batch
        (loss,) = self.exe.run(self.main, feed=batch, fetch_list=[self.loss],
                               scope=self.scope, return_numpy=False)
        return loss

    def _read_rows(self):
        """[n, 2 * vis] float32: value columns, then Adagrad accumulators, of
        the checked rows."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.deferred_rows import unpack_rows

        def read(table, ids):
            rows = unpack_rows(table[jnp.minimum(ids, table.shape[0] - 1)],
                               2 * self.vis)
            return jnp.where((ids < table.shape[0])[:, None], rows, 0.0)

        return jax.jit(read)(self.scope.find_var(self.TABLE), self._row_ids)

    @staticmethod
    def _norm(x) -> float:
        import jax.numpy as jnp
        return float(jnp.sqrt(jnp.sum(jnp.square(x))))

    def first_gradient_norms(self) -> dict:
        """Dense net: Adam's first moment after step one is (1 - beta1) x the
        gradient. Table: the accumulators hold the merged gradient's square
        over what they started at."""
        import jax.numpy as jnp

        beta1 = self.cfg["dense_optimizer"]["beta1"]
        out = {k: self._norm(self.scope.find_var(
            f"{k}_AdamOptimizer_moment1")) / (1.0 - beta1)
            for k in self._dense}
        d = self.cfg["embedding_dim"]
        acc0 = self.cfg["table_optimizer"]["initial_accumulator_value"]
        rows = self._read_rows()
        g2 = jnp.where(self._row_ids[:, None] < self.cfg["table_rows"],
                       rows[:, self.vis:] - acc0, 0.0)
        out["fm_t.embedding"] = float(g2[:, :d].sum()) ** 0.5
        out["fm_t.first_order"] = float(g2[:, d].sum()) ** 0.5
        return out

    def update_norms(self, initial: dict) -> dict:
        out = {k: self._norm(self.scope.find_var(k) - initial["dense"][k])
               for k in self._dense}
        d = self.cfg["embedding_dim"]
        moved = self._read_rows()[:, :self.vis] - initial["rows"]
        out["fm_t.embedding"] = self._norm(moved[:, :d])
        out["fm_t.first_order"] = self._norm(moved[:, d])
        return out

    def hbm(self) -> dict:
        return program_access.memory_of(program_access.compiled_step(
            self.exe, self.main, self.scope, self._last_feed))


def build(cfg: dict, traffic: dict, chips: int) -> System:
    return System(cfg, traffic, chips)
