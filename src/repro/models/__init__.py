from repro.models.model import (decode_step, init_cache, init_params,
                                model_forward, prefill, prefill_chunk,
                                ring_convert_cache, serving_params)

__all__ = ["decode_step", "init_cache", "init_params", "model_forward",
           "prefill", "prefill_chunk", "ring_convert_cache",
           "serving_params"]
