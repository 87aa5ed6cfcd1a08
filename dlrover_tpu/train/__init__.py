from dlrover_tpu.train.estimator import (  # noqa: F401
    ClusterSpec,
    ColumnInfo,
    Estimator,
    EstimatorExecutor,
    EvalSpec,
    FileReader,
    PsFailover,
    RunConfig,
    TrainSpec,
    run_evaluator,
    train_and_evaluate,
)
from dlrover_tpu.train.optimizer import make_optimizer  # noqa: F401
from dlrover_tpu.train.prewarm import prewarm_worlds  # noqa: F401
from dlrover_tpu.train.trainer import Trainer, TrainerArgs  # noqa: F401
from dlrover_tpu.train.train_step import (  # noqa: F401
    TrainStepBuilder,
    batch_sharding,
    init_train_state,
    restore_or_init_train_state,
    state_shardings,
)
