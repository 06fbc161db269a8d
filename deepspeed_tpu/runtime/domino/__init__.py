from deepspeed_tpu.runtime.domino.transformer import (  # noqa: F401
    TP_EXCHANGE, DominoTransformerLayer, ExchangeLayout, column_parallel,
    copy_to_model, count_exchanges, exchange_layout, hold_until, merge_rows,
    parallel_products, row_parallel, split_rows)
