/* Dot-product contraction: vfma accumulation strip loop, one vaddvq
 * horizontal reduction, scalar tail folded into the reduced sum. */
#include <arm_neon.h>

void xnn_f32_vdot_ukernel(size_t n, const float* a, const float* b,
                          float* sum) {
  float32x4_t vacc = vdupq_n_f32(0.0f);
  for (; n >= 4; n -= 4) {
    float32x4_t va = vld1q_f32(a); a += 4;
    float32x4_t vb = vld1q_f32(b); b += 4;
    vacc = vfmaq_f32(vacc, va, vb);
  }
  float vsum = vaddvq_f32(vacc);
  for (; n != 0; n -= 1) {
    vsum = vsum + *a * *b;
    a += 1; b += 1;
  }
  *sum = vsum;
}
