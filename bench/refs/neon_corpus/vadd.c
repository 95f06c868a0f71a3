/* XNNPACK-style f32 element-wise add microkernel (strip-mined Q-register
 * main loop + scalar tail), the shape of xnn_f32_vadd_ukernel__neon. */
#include <arm_neon.h>

void xnn_f32_vadd_ukernel(size_t n, const float* a, const float* b, float* y) {
  for (; n >= 4; n -= 4) {
    float32x4_t va = vld1q_f32(a); a += 4;
    float32x4_t vb = vld1q_f32(b); b += 4;
    float32x4_t vy = vaddq_f32(va, vb);
    vst1q_f32(y, vy); y += 4;
  }
  for (; n != 0; n -= 1) {
    *y = *a + *b;
    a += 1; b += 1; y += 1;
  }
}
