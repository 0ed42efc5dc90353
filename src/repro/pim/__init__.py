"""PIM systems integration: quantization, PIMLinear, crossbar planner."""
from .quant import (PlannedWeight, QTensor, quantize, dequantize,
                    plan_weight, qmatmul_exact, qmatmul_planned,
                    qragged_matmul_exact)
from .pim_linear import PIMLinearSpec, pim_linear_apply
from .planner import (BlockLinear, BlockPlan, GemmShape, LinearGroup,
                      PIMPlan, ServeSlotPlan, block_linears,
                      gemms_from_config, plan_block, plan_model,
                      plan_serve_slots)

__all__ = ["QTensor", "quantize", "dequantize", "qmatmul_exact",
           "qragged_matmul_exact", "PlannedWeight", "plan_weight",
           "qmatmul_planned",
           "PIMLinearSpec", "pim_linear_apply",
           "GemmShape", "PIMPlan", "plan_model", "gemms_from_config",
           "BlockLinear", "LinearGroup", "BlockPlan", "block_linears",
           "plan_block", "ServeSlotPlan", "plan_serve_slots"]
