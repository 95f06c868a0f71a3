/* XNNPACK-style f32 element-wise multiply microkernel. */
#include <arm_neon.h>

void xnn_f32_vmul_ukernel(size_t n, const float* a, const float* b, float* y) {
  for (; n >= 4; n -= 4) {
    float32x4_t va = vld1q_f32(a); a += 4;
    float32x4_t vb = vld1q_f32(b); b += 4;
    vst1q_f32(y, vmulq_f32(va, vb)); y += 4;
  }
  for (; n != 0; n -= 1) {
    *y = *a * *b;
    a += 1; b += 1; y += 1;
  }
}
