from time import perf_counter as _perf_counter

_T_IMPORT = _perf_counter()  # the set-up phase "import.ppo_recurrent": this package and what it pulls in

from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent  # noqa: E402,F401
from sheeprl_tpu.algos.ppo_recurrent import evaluate  # noqa: E402,F401
from sheeprl_tpu.core.compile import record_setup_phase  # noqa: E402

record_setup_phase("import.ppo_recurrent", _T_IMPORT, _perf_counter())
