from time import perf_counter as _perf_counter

_T_IMPORT = _perf_counter()  # the set-up phase "import.dreamer_v3": this package and what it pulls in

from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3  # noqa: E402,F401
from sheeprl_tpu.algos.dreamer_v3 import evaluate  # noqa: E402,F401
from sheeprl_tpu.core.compile import record_setup_phase  # noqa: E402

record_setup_phase("import.dreamer_v3", _T_IMPORT, _perf_counter())
