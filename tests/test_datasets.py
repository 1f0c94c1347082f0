"""The paper's Table 2 rows (``signal/datasets.DATASETS``) against the
chip path: each row's config engages the fused cheap-phase kernel on the
Pallas plan, and one index holds each reference at its published length
except the human genome's (2 x 3.117e9 events is past 2^31 - 2^20)."""
import pytest

from repro.core import stages
from repro.core.index import check_index_limits
from repro.signal import datasets

FITS_ONE_INDEX = {"D1": True, "D2": True, "D3": True, "D4": True,
                  "D5": False}


@pytest.mark.parametrize("key", sorted(datasets.DATASETS))
def test_table2_row_on_the_chip_path(key):
    assert set(FITS_ONE_INDEX) == set(datasets.DATASETS)
    row = datasets.DATASETS[key]
    cfg = datasets.config_for(row).with_mode("ms_fixed")
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    assert stages.fused_cheap_backend(plan, cfg) is not None
    double_genome = 2 * row.paper_genome_len
    if FITS_ONE_INDEX[key]:
        check_index_limits(double_genome, cfg)
    else:
        with pytest.raises(ValueError, match="int32"):
            check_index_limits(double_genome, cfg)
