"""What a builder hands the harness: the system under test behind its
public entry points, and the plain facts the generator, the reference check
and the roofline need about it.

A batch is ``(inputs, cats, labels)``: `cats` the ids per model input, the
first and the last whatever the cell's generator made and the model's
``loss_fn(params, inputs, cats, labels, taps=, return_residuals=)`` takes:
numerical features and ``[B, 1]`` f32 clicks for a click model, document
boundaries and ``[T]`` int32 next-token ids for a language model. The
harness stages them and passes them through."""

import dataclasses
from typing import Any, Callable, List, Optional, Tuple


@dataclasses.dataclass
class Built:
    model: Any                       # .init(key), .embedding(params, cats)
    make_step: Callable[[], Tuple[Callable, Callable]]   # -> (init_fn, step_fn)
    tables: List[Tuple[int, int]]    # (rows, width) per table
    table_map: List[int]             # input -> table
    hotness: List[int]               # ids per sample, per input
    num_numerical: int               # these two are parameters the builder
    numerical_scale: float           # hands the cell's generator
    global_batch: int
    optimizer: dict                  # the config's, for the reference's rule
    #                                  (sgd, adagrad, adam) and the roofline
    reference: str                   # benchmark/references/<reference>.py
    dense_params: Callable[[Any], dict]   # program params -> reference's tree
    mlp_flops_per_sample: int        # forward + backward matmul flops
    ids_1d: bool = False             # one-hot inputs are passed as [B]
    mesh: Optional[Any] = None

    def shape_ids(self, cats):
        """[B, hotness] ids as the model takes them."""
        if self.ids_1d:
            return [c[:, 0] if c.shape[1] == 1 else c for c in cats]
        return list(cats)


def scaled_rows(rows: int, scale: Optional[float]) -> int:
    """A vocabulary cut for a CPU rehearsal (never on the chip)."""
    return rows if scale is None else max(4, int(rows * scale))


def mlp_train_flops(dims) -> int:
    """Matmul flops per sample of one training step through an MLP with
    these layer widths: forward, and twice that backward."""
    return 3 * sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
