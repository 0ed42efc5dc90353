"""PIM systems integration: quantization, the PIM linear, crossbar planner."""
from .quant import (PlannedWeight, QTensor, quantize, dequantize,
                    plan_weight, qmatmul_exact, qmatmul_planned,
                    qragged_matmul_exact)
from .pim_linear import linear, ragged_linear
from .planner import (BlockLinear, BlockPlan, GemmShape, LinearGroup,
                      PIMPlan, ServeSlotPlan, block_linears,
                      gemms_from_config, plan_block, plan_model,
                      plan_serve_slots)

__all__ = ["QTensor", "quantize", "dequantize", "qmatmul_exact",
           "qragged_matmul_exact", "PlannedWeight", "plan_weight",
           "qmatmul_planned",
           "linear", "ragged_linear",
           "GemmShape", "PIMPlan", "plan_model", "gemms_from_config",
           "BlockLinear", "LinearGroup", "BlockPlan", "block_linears",
           "plan_block", "ServeSlotPlan", "plan_serve_slots"]
