/* XNNPACK-style f32 sigmoid contraction via the tanh rational:
 * sigmoid(x) = 0.5 + 0.5 * tanh(x/2), same vfma-ladder + vrecpe/vrecps
 * structure as vtanh.c (paper Figure 2's other largest win). */
#include <arm_neon.h>

void xnn_f32_vsigmoid_ukernel(size_t n, const float* x, float* y) {
  const float32x4_t vhalf = vdupq_n_f32(0.5f);
  const float32x4_t vclamp = vdupq_n_f32(4.0f);
  const float32x4_t vnclamp = vdupq_n_f32(-4.0f);
  const float32x4_t c135135 = vdupq_n_f32(135135.0f);
  const float32x4_t c17325 = vdupq_n_f32(17325.0f);
  const float32x4_t c378 = vdupq_n_f32(378.0f);
  const float32x4_t c62370 = vdupq_n_f32(62370.0f);
  const float32x4_t c3150 = vdupq_n_f32(3150.0f);
  const float32x4_t c28 = vdupq_n_f32(28.0f);
  for (; n >= 4; n -= 4) {
    float32x4_t vx = vld1q_f32(x); x += 4;
    float32x4_t vt = vmulq_f32(vx, vhalf);
    vt = vminq_f32(vmaxq_f32(vt, vnclamp), vclamp);
    float32x4_t vt2 = vmulq_f32(vt, vt);
    float32x4_t vp = vaddq_f32(vt2, c378);
    vp = vfmaq_f32(c17325, vp, vt2);
    vp = vfmaq_f32(c135135, vp, vt2);
    vp = vmulq_f32(vp, vt);
    float32x4_t vq = vfmaq_f32(c3150, vt2, c28);
    vq = vfmaq_f32(c62370, vq, vt2);
    vq = vfmaq_f32(c135135, vq, vt2);
    float32x4_t vr = vrecpeq_f32(vq);
    vr = vmulq_f32(vr, vrecpsq_f32(vq, vr));
    vr = vmulq_f32(vr, vrecpsq_f32(vq, vr));
    float32x4_t vth = vmulq_f32(vp, vr);
    vst1q_f32(y, vfmaq_f32(vhalf, vth, vhalf)); y += 4;
  }
}
