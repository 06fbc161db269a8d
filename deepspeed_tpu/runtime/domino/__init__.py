from deepspeed_tpu.runtime.domino.transformer import (  # noqa: F401
    DW_EXCHANGE, TP_EXCHANGE, DominoTransformerLayer, ExchangeLayout,
    backward_after, backward_together, column_parallel, copy_to_model,
    count_exchanges, exchange_layout, forward_hold, hold_until, land_dw, merge_rows,
    parallel_products, row_parallel, split_rows)
